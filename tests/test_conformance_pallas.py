"""The conformance matrix on the Pallas engines: pallas, pallas_chained.

Every (program × backend × scenario) cell must satisfy  api Session ==
Program.run shim (bit-exact) == oracle == hand-staged  (see
conformance.py, which holds the scenarios, the runners and the cells).
This is the executable form of the paper's evaluation tables; new
engines and kernels must keep it green.  On a single-device host the
distributed columns run at one shard; CI's dist-smoke job re-runs the
dist_sharded fast cells on 8 virtual host devices.
"""
from conformance import matrix

globals().update(matrix({"pallas", "pallas_chained"}))
