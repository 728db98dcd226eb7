"""`correct` must come out false for the control and for each fault the
cells can have, with the rest of a run as it is (rehearsal mode skips
the look for a chip; CPU, tiny size).

Of the contract's faults, a check keeps no state from step to step, so
"a step that returns its state unchanged" has no analogue here."""

import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness

ONE, FOUR = "la-ss-valid-256k", "la-ss-valid-1m-4chip"


def _run(capsys, workload, control=False, seed=2**31 + 7):
    args = SimpleNamespace(workload=workload, seed=seed, seconds=0.5,
                           trace=0, rehearse=600, control=control)
    assert harness.run(args, time.perf_counter()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _fresh_programs():
    """Retrace: the patched program must not come from a cache."""
    import jax

    from jepsen_tpu import compilecache

    jax.clear_caches()
    compilecache.clear()


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_program_as_it_is_is_correct(capsys, seed):
    out = _run(capsys, ONE, seed=seed)
    assert out["correct"] is True, out["compared"]


def test_the_sharded_program_as_it_is_is_correct(capsys, monkeypatch):
    monkeypatch.setenv("JEPSEN_SHARDS", "4")
    monkeypatch.setenv("JEPSEN_SHARD_MIN_TXNS", "0")
    out = _run(capsys, FOUR)
    assert out["correct"] is True and out["failed"] == 0


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_is_not_correct(capsys, seed):
    # serializable drops the realtime order the configuration states:
    # the probe's stale read then reads valid
    out = _run(capsys, ONE, control=True, seed=seed)
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] == 1


def _wrap(monkeypatch, before=None, after=None):
    from jepsen_tpu.checkers.elle import list_append

    orig = list_append.check

    def check(p, models, **kw):
        res = orig(before(p) if before else p, models, **kw)
        return after(res) if after else res

    monkeypatch.setattr(list_append, "check", check)


def _every_other_txn(p):
    """The packed history with its odd txns left out."""
    import dataclasses

    keep_t = np.arange(p.n_txns) % 2 == 0
    keep_m = keep_t[p.mop_txn]
    new_id = np.cumsum(keep_t) - 1
    ln = np.where(p.mop_rd_len > 0, p.mop_rd_len, 0)
    starts = np.where(keep_m, p.mop_rd_start, 0)
    lens = np.where(keep_m, ln, 0)
    elems = np.concatenate([p.rd_elems[s:s + n]
                            for s, n in zip(starts, lens)] or [[]])
    rd_start = np.where(p.mop_rd_len >= 0,
                        np.cumsum(lens) - lens, -1)[keep_m]
    return dataclasses.replace(
        p, txn_type=p.txn_type[keep_t], txn_process=p.txn_process[keep_t],
        txn_invoke_pos=p.txn_invoke_pos[keep_t],
        txn_complete_pos=p.txn_complete_pos[keep_t],
        txn_orig_index=p.txn_orig_index[keep_t],
        mop_txn=new_id[p.mop_txn[keep_m]].astype(np.int32),
        mop_kind=p.mop_kind[keep_m], mop_key=p.mop_key[keep_m],
        mop_val=p.mop_val[keep_m],
        mop_rd_start=rd_start.astype(np.int32),
        mop_rd_len=p.mop_rd_len[keep_m],
        rd_elems=elems.astype(np.int32))


def test_an_answer_from_the_host_oracle(capsys, monkeypatch):
    """A sweep that does not converge sends the check, unstamped, to the
    host oracle: its answers are right but did not come from the
    device, so the run fails them and is not correct."""
    import dataclasses

    from jepsen_tpu.checkers.elle import list_append

    orig = list_append.detect_cycles

    def not_converged(*a, **kw):
        return dataclasses.replace(orig(*a, **kw), converged=False)

    monkeypatch.setattr(list_append, "detect_cycles", not_converged)
    out = _run(capsys, ONE)
    assert out["compared"]["wrong_answers"]["value"] == 0
    assert out["failed"] == out["attempted"]
    assert out["compared"]["host_answers"]["value"] == out["attempted"]
    assert out["correct"] is False


def test_an_answer_altered_where_it_is_produced(capsys, monkeypatch):
    def flip(res):
        return {**res, "valid?": not res["valid?"]}

    _wrap(monkeypatch, after=flip)
    assert _run(capsys, ONE)["correct"] is False


def test_half_of_the_history_left_out(capsys, monkeypatch):
    _wrap(monkeypatch, before=_every_other_txn)
    assert _run(capsys, ONE)["correct"] is False


def test_the_exchange_between_chips_left_out(capsys, monkeypatch):
    """The sharded sweep without its exchange: no chip receives another
    chip's meta rows (the all-gather leaves them zero), and the verdict
    is the last chip's alone (psum and pmax pass its value on).  The
    probe's one backward edge lies in the first chip's window of the
    128, so only the exchange could show the last chip its cycle."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("JEPSEN_SHARDS", "4")
    monkeypatch.setenv("JEPSEN_SHARD_MIN_TXNS", "0")
    psum, pmax, n = jax.lax.psum, jax.lax.pmax, 4

    def own(x, axis_name, j):
        return jnp.where(jax.lax.axis_index(axis_name) == j, x,
                         jnp.zeros_like(x))

    def all_gather(x, axis_name, axis=0, tiled=False):
        parts = [own(x, axis_name, j) for j in range(n)]
        return jnp.concatenate(parts, axis) if tiled else \
            jnp.stack(parts, axis)

    monkeypatch.setattr(jax.lax, "all_gather", all_gather)
    monkeypatch.setattr(jax.lax, "psum",
                        lambda x, a: psum(own(x, a, n - 1), a))
    monkeypatch.setattr(jax.lax, "pmax",
                        lambda x, a: pmax(own(x, a, n - 1), a))
    _fresh_programs()
    try:
        out = _run(capsys, FOUR)
    finally:
        monkeypatch.undo()
        _fresh_programs()
    assert out["failed"] == 0  # it ran on the device path, sharded
    assert out["correct"] is False
