"""A whole run on the CPU at a small size, the chip check skipped: sound,
it comes out correct; with the timed path broken underneath, not."""
import dataclasses

import jax.numpy as jnp
import pytest

import gen
import manifest
import run

SMALL = {"scale": 10}
TRAFFIC = {"batch": 256, "inserts": 256, "deletes": 256, "stream_batches": 6}


def small_cell(name):
    cell = manifest.Cell(name)
    cell.config = cfg = {**cell.config, **SMALL}
    if "src" in cfg["args"]:
        cfg["args"] = {**cfg["args"], "src": gen.search_key(cfg)}
    cell.traffic = {**cell.traffic, **TRAFFIC}
    return cell


def run_small(name, fault=None, seed=2 ** 31 + 17, cell=None):
    """A whole small run; a budget that expires is recorded and fails the
    test in place of ending the process."""
    expired = []
    res = run.run(cell or small_cell(name), seed, 1.0, False,
                  require_tpu=False, fault=fault, on_expire=expired.append)
    assert not expired, expired
    return res


def unchanged(sess, batch):
    """The step returns its state as it found it."""


def half_batch(sess, batch):
    """Half of each side's lanes left out."""
    h = batch.size // 2
    sess.apply(dataclasses.replace(
        batch, add_mask=batch.add_mask.at[h:].set(False),
        del_mask=batch.del_mask.at[h:].set(False)))


def altered(prop, change):
    def fault(sess, batch):
        sess.apply(batch)
        sess._props[prop] = change(sess._props[prop])
    return fault


CELLS = {
    "sssp-kron18-mixed": altered("dist", lambda d: jnp.where(d == 0, 1, d)),
    "pr-kron18-mixed": altered("pageRank",
                               lambda p: p.at[jnp.argmax(p)].set(0.0)),
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name, monkeypatch):
    import repro.api
    values = []
    value = repro.api.Session.value
    monkeypatch.setattr(repro.api.Session, "value", property(
        lambda s: values.append(1) or value.fget(s)))
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"updates_per_s", "setup_s"}
    assert not values, "a module without VALUE never reads Session.value"


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_broken_step_is_not_correct(name, fault):
    f = {"unchanged": unchanged, "half_batch": half_batch,
         "altered": CELLS[name]}[fault]
    res = run_small(name, fault=f)
    assert not res["correct"], res["checks"]
