"""DSL frontend tests: lexer/parser/analysis units + end-to-end
compilation of the paper's appendix programs (Figs. 19-21) validated
against oracles on all three backends — the paper's 'one spec, three
backends' claim exercised through the real compiler pipeline."""
import pathlib

import numpy as np
import pytest

from conftest import random_digraph, random_symgraph, sym_stream
from repro.graph import random_updates
from repro.core.dsl import (compile_source, parse, tokenize, analyze,
                            LexError, ParseError, SemanticError)
from repro.core.dsl import ast_nodes as A
from repro.core.dsl.emit import emit_report
from repro.core.engine import JnpEngine
from repro.core.dist import DistEngine
from repro.core.pallas_engine import PallasEngine
from repro.algos import oracles

PROGS = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro" / \
    "dsl_programs"

ENGINES = [JnpEngine, DistEngine, PallasEngine]


# ---------------------------------------------------------------------------
# front-end units
# ---------------------------------------------------------------------------

def test_lexer_basic():
    toks = tokenize("forall (v in g.nodes()) { v.dist = 0; } // c")
    kinds = [t.kind for t in toks]
    assert kinds[-1] == "eof"
    assert toks[0].kind == "kw" and toks[0].text == "forall"
    texts = [t.text for t in toks]
    assert "//" not in " ".join(texts)          # comments stripped


def test_parser_multiassign_and_min():
    src = """
    Static f(Graph g, propNode<int> dist, propEdge<int> weight) {
      forall (v in g.nodes().filter(modified == True)) {
        forall (nbr in g.neighbors(v)) {
          edge e = g.get_edge(v, nbr);
          <nbr.dist, nbr.mod2, nbr.parent> =
              <Min(nbr.dist, v.dist + e.weight), True, v>;
        }
      }
    }
    """
    ast = parse(src)
    fa = ast.funcs[0].body.stmts[0]
    assert isinstance(fa, A.ForAll) and fa.filter is not None
    inner = fa.body.stmts[0]
    ma = inner.body.stmts[1]
    assert isinstance(ma, A.MultiAssign)
    assert isinstance(ma.values[0], A.MinMax)


def test_parser_rejects_arity_mismatch():
    with pytest.raises(ParseError):
        parse("Static f(Graph g) { <a.x, a.y> = <1>; }")


@pytest.mark.parametrize("src,err", [
    # lexer: characters outside the token alphabet
    ("Static f(Graph g) { int x = 3 @ 4; }", LexError),
    ("Static f(Graph g) { int q = `; }", LexError),
    # parser: malformed forall / multi-assign / missing terminator
    ("Static f(Graph g) { forall (v in ) { } }", ParseError),
    ("Static f(Graph g) { forall (v g.nodes()) { } }", ParseError),
    ("Static f(Graph g) { <a.x> = <1, 2>; }", ParseError),
    ("Static f(Graph g) { <a.x, a.y> = <1>; }", ParseError),
    ("Static f(Graph g) { int x = 1 }", ParseError),
    # semantic analysis: undeclared properties, undeclared names,
    # read-before-write — analysis failure rejects the program
    ("Static f(Graph g, propNode<int> dist) {\n"
     "  forall (v in g.nodes()) { v.distt = 0; } }", SemanticError),
    ("Static f(Graph g) {\n"
     "  forall (v in g.nodes()) { int y = v.missing + 1; } }",
     SemanticError),
    ("Static f(Graph g) { int y = z + 1; }", SemanticError),
    ("Static f(Graph g) { int x; int y = x + 1; }", SemanticError),
    ("Static f(Graph g) { float d; d += 1.0; }", SemanticError),
    ("Static f(Graph g) { int x; bool c = True;\n"
     "  if (c) { x = 1; } int y = x; }", SemanticError),
    ("Static f(Graph g) { int x; bool c = True;\n"
     "  while (c) { x = 1; } int y = x; }", SemanticError),
], ids=["lex-at", "lex-backtick", "forall-empty-iter", "forall-no-in",
        "multiassign-1v2", "multiassign-2v1", "missing-semicolon",
        "undeclared-prop-write", "undeclared-prop-read", "undeclared-name",
        "read-before-write", "accum-before-write",
        "one-branch-init", "zero-iteration-loop-init"])
def test_frontend_error_paths(src, err):
    """LexError / ParseError / SemanticError each fire on the malformed
    program and carry a line number in the message."""
    with pytest.raises(err):
        compile_source(src)


@pytest.mark.parametrize("src", [
    # a do-while body runs before its condition is first evaluated
    "Static f(Graph g) { int i; do { i = 0; i = i + 1; } "
    "while (i < 3); }",
    # assigned on both branches → initialized afterwards
    "Static f(Graph g) { int x; bool c = True;\n"
    "  if (c) { x = 1; } else { x = 2; } int y = x; }",
], ids=["dowhile-body-initializes", "both-branches-initialize"])
def test_init_order_accepts_valid_paths(src):
    compile_source(src)        # must not raise


def test_analysis_race_inference():
    src = (PROGS / "sssp.sp").read_text()
    infos = analyze(parse(src))
    sweeps = infos["staticSSSP"].sweeps
    races = [r for s in sweeps for r in s.races]
    kinds = sorted({r.kind for r in races})
    assert "min" in kinds and "argmin" in kinds and "or" in kinds
    # read/write sets: the relax sweep reads dist+modified, writes dist etc
    edge_sweeps = [s for s in sweeps if s.orientation == "push"]
    assert any("dist" in s.reads and "dist" in s.writes
               for s in edge_sweeps)


def test_emit_report_mentions_combiners():
    prog = compile_source(str(PROGS / "sssp.sp"))
    rep = emit_report(prog, backend="dist")
    assert "Reduce(min" in rep
    assert "argmin" in rep
    assert "update_del" in rep or "updateCSRDel" in rep


# ---------------------------------------------------------------------------
# end-to-end: paper programs vs oracles on all three backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_cls", ENGINES, ids=lambda c: c.name)
def test_dsl_dynamic_sssp(engine_cls):
    prog = compile_source(str(PROGS / "sssp.sp"))
    n, csr, edges, w = random_digraph(seed=11)
    eng = engine_cls()
    ups = random_updates(csr, percent=15, seed=2)
    res = prog.run("DynSSSP", eng, csr,
                   args={"updateBatch": ups, "batchSize": 8, "src": 0},
                   diff_capacity=64)
    e2, w2 = oracles.edges_after_updates(n, edges, w, ups.adds, ups.dels)
    ref = oracles.sssp_oracle(n, e2, w2, 0)
    got = np.minimum(res.props["dist"].astype(np.int64), oracles.INF)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("engine_cls", ENGINES, ids=lambda c: c.name)
def test_dsl_dynamic_pagerank(engine_cls):
    prog = compile_source(str(PROGS / "pagerank.sp"))
    n, csr, edges, w = random_digraph(seed=12)
    eng = engine_cls()
    ups = random_updates(csr, percent=10, seed=3)
    res = prog.run("DynPR", eng, csr,
                   args={"updateBatch": ups, "batchSize": 8,
                         "beta": 1e-3, "delta": 0.85, "maxIter": 100},
                   diff_capacity=64)
    e2, _ = oracles.edges_after_updates(n, edges, w, ups.adds, ups.dels)
    ref = oracles.pagerank_oracle(n, e2)
    np.testing.assert_allclose(res.props["pageRank"], ref,
                               rtol=5e-2, atol=1e-4)


@pytest.mark.parametrize("engine_cls", [JnpEngine, PallasEngine],
                         ids=lambda c: c.name)
def test_dsl_dynamic_tc(engine_cls):
    prog = compile_source(str(PROGS / "tc.sp"))
    n, csr, edges = random_symgraph(seed=4)
    eng = engine_cls()
    ups = sym_stream(csr, percent=15, seed=6)
    res = prog.run("DynTC", eng, csr,
                   args={"updateBatch": ups, "batchSize": 16},
                   diff_capacity=256)
    e2, _ = oracles.edges_after_updates(
        n, edges, np.ones(len(edges), np.int32), ups.adds, ups.dels)
    assert int(res.value) == oracles.tc_oracle(n, e2)


class _SweepRecorder(JnpEngine):
    """Records the EdgeSweep of every fixed point it runs."""

    def __init__(self):
        super().__init__()
        self.sweeps = []

    def fixed_point(self, g, sw, props, cond_fn, max_iter):
        self.sweeps.append(sw)
        return super().fixed_point(g, sw, props, cond_fn, max_iter)


def test_sssp_fixed_points_declare_their_frontier():
    prog = compile_source(str(PROGS / "sssp.sp"))
    n, csr, edges, w = random_digraph(seed=11)
    eng = _SweepRecorder()
    ups = random_updates(csr, percent=15, seed=2)
    prog.run("DynSSSP", eng, csr,
             args={"updateBatch": ups, "batchSize": 8, "src": 0},
             diff_capacity=64)
    assert eng.sweeps
    assert all(sw.frontier == "modified" for sw in eng.sweeps)
    # each fixedPoint ends in `modified = modified_nxt;` and an
    # attachNodeProperty, which the lowering folds into a rebuilt
    # post_fn: the rebuilt sweep keeps its frontier
    assert all(sw.post_fn.__qualname__.startswith("run_loop.")
               for sw in eng.sweeps)


@pytest.mark.parametrize("spelling,frontier", [
    ("modified", "modified"),
    ("v.modified == True", "modified"),
    ("modified == False", None),
])
def test_frontier_follows_the_filter(spelling, frontier):
    text = (PROGS / "sssp.sp").read_text().replace(
        "filter(modified == True)", f"filter({spelling})")
    prog = compile_source(text)
    n, csr, edges, w = random_digraph(seed=5)
    eng = _SweepRecorder()
    prog.run("staticSSSP", eng, csr, args={"src": 0})
    assert [sw.frontier for sw in eng.sweeps] == [frontier]


def test_pagerank_sweeps_declare_no_frontier():
    """recomputePR and staticPR pull over in-edges with float sums, and
    propagateNodeFlags builds its own sweep: none declares a frontier."""
    prog = compile_source(str(PROGS / "pagerank.sp"))
    n, csr, edges, w = random_digraph(seed=12)
    eng = _SweepRecorder()
    ups = random_updates(csr, percent=10, seed=3)
    prog.run("DynPR", eng, csr,
             args={"updateBatch": ups, "batchSize": 8,
                   "beta": 1e-3, "delta": 0.85, "maxIter": 100},
             diff_capacity=64)
    kinds = {tuple(sorted(r.kind for r in sw.reduces.values()))
             for sw in eng.sweeps}
    assert ("or",) in kinds and ("sum",) in kinds
    assert all(sw.frontier is None for sw in eng.sweeps)


def test_dsl_static_matches_handwritten():
    """DSL-compiled static SSSP ≡ the hand-staged repro.algos version."""
    from repro.algos import sssp as hand
    prog = compile_source(str(PROGS / "sssp.sp"))
    n, csr, edges, w = random_digraph(seed=21)
    eng = JnpEngine()
    res = prog.run("staticSSSP", eng, csr, args={"src": 0})
    g = eng.prepare(csr, diff_capacity=16)
    ref = hand.static_sssp(eng, g, 0)
    assert np.array_equal(res.props["dist"],
                          np.asarray(ref["dist"])[:n])
    assert np.array_equal(res.props["parent"],
                          np.asarray(ref["parent"])[:n])
