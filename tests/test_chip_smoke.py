"""chip_smoke.py's contract off the chip: it refuses to run without a
TPU and prints no result, and its spread check rejects sharded state
piled on one device."""
import importlib.util
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"], ["--scale", "22"]])
def test_refuses_without_tpu(smoke, monkeypatch, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", *argv])
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out


@pytest.mark.parametrize("resident,ok", [
    ([100, 100, 120, 100], True),         # near-equal shards
    ([400, 100, 100, 100], False),        # piled on device 0
    ([100, 0, 0, 0], False),              # nothing on three devices
    ([None] * 4, True),                   # no memory stats off the chip
])
def test_check_spread(smoke, resident, ok):
    if ok:
        smoke.check_spread(resident)
    else:
        with pytest.raises(AssertionError, match="not spread"):
            smoke.check_spread(resident)
