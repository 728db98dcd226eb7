"""compilecache/ — the shape-bucketed AOT executable cache (ISSUE 18).

Pins the three layers and their contracts:

- **bucket policy**: the pow2 rounding rule equals
  ``device_infer.pow2_at_least`` (drift pin), padding is monotone, and
  a shrink probe + campaign cell at nearby sizes land in the SAME
  shape class (the whole point of bucketing);
- **store**: entries are self-verifying — roundtrip, truncation, and
  bit-flips are detected, and a corrupt entry is deleted on sight;
- **seam**: miss -> disk entry -> (cleared memory) -> disk hit with
  identical values; corrupt entries fall through and re-serialize;
  chaos plans fire ONLY when they name a compilecache site; disabled
  env means plain jit untouched;
- **cold vs warm**: a real core check loaded from the AOT store
  returns bitwise the verdict of the cold compile, with zero misses;
- **warm ladder**: ``warm_ladder`` populates exactly the classes the
  live dispatcher routes, so the next live check is all hits;
- **fleet**: advert/pull/push/absorb over a real coordinator + HTTP
  server — a pre-warmed first claim dispatches with ZERO compile-cache
  misses, wrong-digest pulls are rejected, and pushed entries land in
  the coordinator's flat store.
"""

import os

import numpy as np
import pytest

from jepsen_tpu import compilecache
from jepsen_tpu.compilecache import bucket, fleet as cc_fleet, store
from jepsen_tpu.compilecache import warm as cc_warm


@pytest.fixture(autouse=True)
def _cc_isolated():
    """Save/restore the process-global cache-dir override and drop the
    in-memory table + stats around every test — no test leaks its pin
    or its executables into the next."""
    prev = compilecache._dir_override
    compilecache.clear()
    compilecache.reset_stats()
    yield
    compilecache._dir_override = prev
    compilecache.clear()
    compilecache.reset_stats()


def _jit_double():
    import jax

    return jax.jit(lambda x: x * 2 + 1)


def _arange(n):
    import jax.numpy as jnp

    return jnp.arange(n, dtype=jnp.float32)


# -- placement ---------------------------------------------------------------

def test_compile_cache_placed_by_env(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set (conftest sets it before jax
    is imported), JAX's cache directory is that directory, no code
    moves it, and the AOT store lives under it — the fleet's store-base
    adoption included."""
    import jax

    from jepsen_tpu.utils import backend

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        pytest.skip("JT_NO_TEST_CACHE: the suite runs without the cache")
    assert jax.config.jax_compilation_cache_dir == d
    assert backend.enable_compile_cache() == d
    assert jax.config.jax_compilation_cache_dir == d
    monkeypatch.delenv("JT_COMPILECACHE", raising=False)
    compilecache._dir_override = compilecache._UNSET
    aot = os.path.join(d, "aot")
    assert compilecache.cache_dir() == aot
    assert compilecache.adopt_base("/elsewhere") == aot
    assert compilecache.cache_dir() == aot


def test_compile_cache_default_without_env(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR, JAX's cache is the fixed
    <repo>/.jax_cache and the AOT store stays memory-only."""
    import jax

    from jepsen_tpu.utils import backend

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JT_COMPILECACHE", raising=False)
    compilecache._dir_override = compilecache._UNSET
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert backend.enable_compile_cache() == \
            os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert compilecache.cache_dir() is None


# -- bucket policy -----------------------------------------------------------


def test_pow2_rule_pinned_to_device_infer():
    """The drift pin: bucket's rounding rule IS device_infer's — two
    copies of the rule may never disagree on any size."""
    from jepsen_tpu.checkers.elle import device_infer

    for n in [*range(1, 300), 1000, 4097, 65536, 100001]:
        assert bucket.pow2_at_least(n) == device_infer.pow2_at_least(n)


def test_pow2_monotone_floor():
    prev = 0
    for n in range(1, 2050):
        b = bucket.pow2_at_least(n)
        assert b >= n and b >= 8
        assert b & (b - 1) == 0, f"{b} not a power of two"
        assert b >= prev
        prev = b
    assert bucket.pow2_at_least(3, floor=16) == 16


def test_probe_and_cell_share_class():
    """A shrink probe at 300 txns and a campaign cell at 400 pad into
    the SAME shape class — one executable serves both."""
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.workloads import synth

    kw = dict(concurrency=10, mops_per_txn=4, read_frac=0.25, seed=7)
    sigs = []
    for n in (300, 400):
        p = synth.packed_la_history(n_txns=n, n_keys=64, **kw)
        sigs.append(bucket.signature((pad_packed(p),)))
    assert sigs[0] == sigs[1]
    st = {"n_keys": 64, "max_k": 128}
    assert bucket.class_digest("elle.core-check", (), st) == \
        bucket.class_digest("elle.core-check", (), st)
    # a different static is a different specialization
    assert bucket.class_digest("elle.core-check", (), st) != \
        bucket.class_digest("elle.core-check", (), {**st, "max_k": 256})
    # and a different site is a different class
    assert bucket.class_digest("elle.infer", (), st) != \
        bucket.class_digest("elle.core-check", (), st)


def test_abstract_and_concrete_sign_identically():
    import jax

    x = _arange(64)
    sds = jax.ShapeDtypeStruct(x.shape, x.dtype)
    assert bucket.signature((x,)) == bucket.signature((sds,))


def test_ladder():
    assert bucket.ladder() == sorted(bucket.LADDER)
    assert bucket.ladder(max_txns=5000) == \
        sorted(set(bucket.LADDER) | {2048, 4096, 8192})
    assert bucket.ladder(sizes=[100, 100, 3]) == [8, 128]
    # --max-txns CAPS the ladder (the CLI help's contract): rungs
    # above the bucket are dropped, never warmed
    assert bucket.ladder(max_txns=128) == [64, 128]
    assert bucket.ladder(max_txns=200) == [64, 128, 256]
    assert bucket.ladder(max_txns=5) == [8]


def test_ir_bucket_class_shared_across_sizes():
    """history.ir exposes the class label; nearby sizes report the
    same one (what the pre-warm ladder covers)."""
    from jepsen_tpu.history.ir import HistoryIR
    from jepsen_tpu.workloads import synth

    kw = dict(concurrency=10, mops_per_txn=4, read_frac=0.25, seed=7)
    labels = set()
    for n in (300, 400):
        p = synth.packed_la_history(n_txns=n, n_keys=64, **kw)
        labels.add(HistoryIR(p).bucket_class())
    assert len(labels) == 1


# -- store -------------------------------------------------------------------


def test_store_roundtrip_and_corruption(tmp_path):
    d = str(tmp_path)
    meta = {"site": "t", "class": "c"}
    payload = (b"executable-bytes", {"tree": 1})
    blob = store.pack_entry(meta, payload)
    assert blob.startswith(store.MAGIC)
    doc = store.unpack_entry(blob)
    assert doc["meta"] == meta and doc["payload"] == payload
    # truncation and bit-flips are both detected
    assert store.unpack_entry(blob[:-3]) is None
    flipped = bytearray(blob)
    flipped[len(blob) // 2] ^= 0xFF
    assert store.unpack_entry(bytes(flipped)) is None
    assert store.unpack_entry(b"not an entry") is None

    n = store.put(d, "f" * 40, meta, payload)
    assert n > 0
    got = store.get(d, "f" * 40)
    assert got is not None and got[0]["meta"] == meta
    assert [e["name"] for e in store.entries(d)] == \
        ["f" * 40 + store.SUFFIX]
    assert store.total_bytes(d) == n
    store.delete(d, "f" * 40)
    assert store.entries(d) == []


def test_store_get_deletes_corrupt_on_sight(tmp_path):
    d = str(tmp_path)
    store.put(d, "a" * 40, {"site": "t"}, b"p")
    path = os.path.join(d, "a" * 40 + store.SUFFIX)
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    assert store.get(d, "a" * 40) is None
    assert not os.path.exists(path), "corrupt entry must be deleted"


# -- the call seam -----------------------------------------------------------


def test_call_miss_then_disk_hit(tmp_path):
    """miss -> persisted entry; cleared memory -> disk load counts a
    hit and returns the identical value."""
    compilecache.set_cache_dir(str(tmp_path))
    f = _jit_double()
    x = _arange(64)
    want = np.asarray(x) * 2 + 1
    out = compilecache.call("t.seam", f, x)
    assert np.array_equal(np.asarray(out), want)
    st = compilecache.stats()
    assert st["misses"] == 1 and st["hits"] == 0
    assert st["entries"] == 1 and st["fallthroughs"] == 0
    # in-memory fast path: second call is a hit without touching disk
    compilecache.call("t.seam", f, x)
    assert compilecache.stats()["hits"] == 1
    # drop the executable table: the disk entry alone must serve
    compilecache.clear()
    compilecache.reset_stats()
    out2 = compilecache.call("t.seam", f, x)
    st = compilecache.stats()
    assert np.array_equal(np.asarray(out2), want)
    assert st["hits"] == 1 and st["misses"] == 0 \
        and st["fallthroughs"] == 0


def test_corrupt_entry_falls_through_and_reserializes(tmp_path):
    compilecache.set_cache_dir(str(tmp_path))
    f = _jit_double()
    x = _arange(64)
    compilecache.call("t.corrupt", f, x)
    [e] = store.entries(str(tmp_path))
    path = os.path.join(str(tmp_path), e["name"])
    with open(path, "r+b") as fh:
        fh.seek(e["size"] // 2)
        b = fh.read(1)
        fh.seek(-1, os.SEEK_CUR)
        fh.write(bytes([b[0] ^ 0xFF]))
    compilecache.clear()
    compilecache.reset_stats()
    out = compilecache.call("t.corrupt", f, x)
    st = compilecache.stats()
    assert np.array_equal(np.asarray(out), np.asarray(x) * 2 + 1)
    assert st["misses"] == 1 and st["hits"] == 0
    # the recompile re-serialized a good entry in place
    [e2] = store.entries(str(tmp_path))
    with open(os.path.join(str(tmp_path), e2["name"]), "rb") as fh:
        assert store.unpack_entry(fh.read()) is not None


def test_loaded_entry_raising_at_dispatch_self_heals(tmp_path,
                                                     monkeypatch):
    """An entry that deserializes fine but whose executable raises at
    dispatch (execute-time skew) is DELETED, the call falls through to
    plain jit, and the next call recompiles + re-persists — the cache
    never pays deserialize + fall-through forever."""
    from jax.experimental import serialize_executable as se

    compilecache.set_cache_dir(str(tmp_path))
    f = _jit_double()
    x = _arange(64)
    want = np.asarray(x) * 2 + 1
    compilecache.call("t.skew", f, x)
    assert len(store.entries(str(tmp_path))) == 1

    class _Broken:
        def __call__(self, *a):
            raise RuntimeError("Symbols not found (execute-time skew)")

    compilecache.clear()
    compilecache.reset_stats()
    monkeypatch.setattr(se, "deserialize_and_load",
                        lambda *a, **kw: _Broken())
    out = compilecache.call("t.skew", f, x)
    assert np.array_equal(np.asarray(out), want)  # fell through, right
    st = compilecache.stats()
    assert st["fallthroughs"] == 1
    assert store.entries(str(tmp_path)) == [], \
        "the skewed entry must be deleted, not retried forever"
    monkeypatch.undo()
    compilecache.clear()
    compilecache.reset_stats()
    out2 = compilecache.call("t.skew", f, x)
    assert np.array_equal(np.asarray(out2), want)
    st = compilecache.stats()
    assert st["misses"] == 1 and st["fallthroughs"] == 0
    assert len(store.entries(str(tmp_path))) == 1, "re-persisted"


def test_chaos_plan_fires_only_when_named(tmp_path):
    """The opt-in contract: a plan naming compilecache.compile forces
    the fall-through tail (correct value, counted); a bare p=1 plan
    does NOT fire at cache seams."""
    from jepsen_tpu.resilience import FaultPlan, use

    compilecache.set_cache_dir(str(tmp_path))
    f = _jit_double()
    x = _arange(64)
    want = np.asarray(x) * 2 + 1
    plan = FaultPlan(seed=3, p=1.0, kinds=("xla",),
                     sites="compilecache.compile")
    with use(plan):
        out = compilecache.call("t.chaos", f, x)
    st = compilecache.stats()
    assert np.array_equal(np.asarray(out), want)
    assert st["fallthroughs"] == 1 and st["misses"] == 0
    assert store.entries(str(tmp_path)) == [], \
        "a faulted compile must not persist an entry"

    compilecache.reset_stats()
    bare = FaultPlan(seed=3, p=1.0, kinds=("xla",))
    with use(bare):
        out = compilecache.call("t.chaos", f, x)
    st = compilecache.stats()
    assert np.array_equal(np.asarray(out), want)
    assert st["fallthroughs"] == 0 and st["misses"] == 1
    assert bare.injected == [], \
        "an unnamed plan must not advance at cache seams"


def test_disabled_env_means_plain_jit(tmp_path, monkeypatch):
    monkeypatch.setenv("JT_COMPILECACHE", "0")
    compilecache.set_cache_dir(str(tmp_path))
    f = _jit_double()
    x = _arange(64)
    out = compilecache.call("t.off", f, x)
    assert np.array_equal(np.asarray(out), np.asarray(x) * 2 + 1)
    st = compilecache.stats()
    assert st["hits"] == 0 and st["misses"] == 0 \
        and st["fallthroughs"] == 0
    assert store.entries(str(tmp_path)) == []


def test_ensure_abstract_then_concrete_hit(tmp_path):
    """ensure() at ShapeDtypeStruct shapes populates the class a later
    concrete call hits — the pre-warm mechanism itself."""
    import jax

    compilecache.set_cache_dir(str(tmp_path))
    f = _jit_double()
    x = _arange(128)
    how = compilecache.ensure(
        "t.warm", f, jax.ShapeDtypeStruct(x.shape, x.dtype))
    assert how == "compiled"
    assert compilecache.ensure(
        "t.warm", f, jax.ShapeDtypeStruct(x.shape, x.dtype)) == "cached"
    compilecache.reset_stats()
    out = compilecache.call("t.warm", f, x)
    st = compilecache.stats()
    assert np.array_equal(np.asarray(out), np.asarray(x) * 2 + 1)
    assert st["hits"] == 1 and st["misses"] == 0


# -- cold vs warm on the real checker ----------------------------------------


def _leaves_equal(a, b):
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def test_cold_vs_warm_core_check_equal(tmp_path):
    """The acceptance bar: a core check served from the AOT store is
    bitwise the cold-compile verdict, with zero misses.

    The suite's persistent jax compilation cache is disabled for the
    cold compile: an XLA:CPU executable the jit cache LOADED (rather
    than compiled) re-serializes incompletely ("Symbols not found" at
    deserialize) — the seam detects that, drops the entry, and
    recompiles (graceful), but this test pins the genuine
    serialize→deserialize round trip, so it needs a fresh compile.
    Flipping the config alone is not enough once the cache singleton
    has initialized; reset_cache() makes the flip take effect."""
    import jax

    from jepsen_tpu.checkers.elle.device_core import core_check_auto
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.workloads import synth

    def _reset_jit_cache():
        try:
            from jax._src import compilation_cache as cc_mod
            cc_mod.reset_cache()
        except Exception:
            pass

    compilecache.set_cache_dir(str(tmp_path))
    prev_jit_cache = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jit_cache()
    try:
        p = synth.packed_la_history(n_txns=100, n_keys=64,
                                    concurrency=10, mops_per_txn=4,
                                    read_frac=0.25, seed=7)
        h = pad_packed(p)
        cold = core_check_auto(h, p.n_keys, max_k=64)
        st = compilecache.stats()
        assert st["misses"] >= 1
        assert st["entries"] >= 1, "the cold compile must persist"

        compilecache.clear()
        jax.clear_caches()
        compilecache.reset_stats()
        warm = core_check_auto(h, p.n_keys, max_k=64)
        st = compilecache.stats()
        assert _leaves_equal(cold, warm)
        assert st["hits"] >= 1 and st["misses"] == 0 \
            and st["fallthroughs"] == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_jit_cache)
        _reset_jit_cache()


def test_warm_ladder_covers_live_dispatch(tmp_path):
    """The warmed class IS the live class: after warm_ladder at one
    rung, a live check over a default-generator history of that rung
    dispatches with zero misses."""
    from jepsen_tpu.checkers.elle.device_core import core_check_auto
    from jepsen_tpu.checkers.elle.device_infer import pad_packed
    from jepsen_tpu.workloads import synth

    compilecache.set_cache_dir(str(tmp_path))
    recs = cc_warm.warm_ladder(sizes=(64,), families=("la",), max_k=64)
    assert len(recs) == 1 and recs[0]["ok"], recs
    assert all(p["how"] in ("compiled", "loaded", "cached")
               for p in recs[0]["programs"])
    assert len(store.entries(str(tmp_path))) >= 1

    p = synth.packed_la_history(n_txns=64, n_keys=64,
                                **cc_warm._LA_KW)
    h = pad_packed(p)
    compilecache.reset_stats()
    core_check_auto(h, p.n_keys, max_k=64)
    st = compilecache.stats()
    assert st["misses"] == 0 and st["fallthroughs"] == 0, st
    assert st["hits"] >= 1


# -- fleet distribution ------------------------------------------------------


def test_safe_name():
    fp = "a" * 40
    assert cc_fleet._safe_name(fp + store.SUFFIX)
    assert not cc_fleet._safe_name("x/../y" + store.SUFFIX)
    assert not cc_fleet._safe_name("." + store.SUFFIX)
    assert not cc_fleet._safe_name("a\\b" + store.SUFFIX)
    assert not cc_fleet._safe_name(fp)  # wrong suffix


def test_export_index_memo_and_read(tmp_path):
    d = str(tmp_path)
    store.put(d, "b" * 40, {"site": "t"}, b"p")
    [row] = cc_fleet.export_index(d)
    assert row["name"] == "b" * 40 + store.SUFFIX
    path = os.path.join(d, row["name"])
    assert row["digest"] == store.file_digest(path)
    # memoized by path + (size, mtime_ns): a second export returns the
    # same row
    assert cc_fleet.export_index(d) == [row]
    assert path in cc_fleet._digests
    blob = cc_fleet.read_entry(d, row["name"])
    assert blob is not None and store.unpack_entry(blob) is not None
    assert cc_fleet.read_entry(d, "../" + row["name"]) is None
    assert cc_fleet.read_entry(d, "nope" + store.SUFFIX) is None
    # the memo never outlives its file: a deleted entry's digest is
    # pruned on the next export, and compilecache.clear() empties it
    store.delete(d, "b" * 40)
    assert cc_fleet.export_index(d) == []
    assert path not in cc_fleet._digests
    store.put(d, "b" * 40, {"site": "t"}, b"p")
    cc_fleet.export_index(d)
    compilecache.clear()
    assert cc_fleet._digests == {}


def _mint_batch(base, entries):
    """Stage a pushed-batch dir: (name, blob, mac|None) triples."""
    batch = os.path.join(base, "compilecache", "cc-test")
    os.makedirs(batch, exist_ok=True)
    for name, blob, mac in entries:
        with open(os.path.join(batch, name), "wb") as f:
            f.write(blob)
        if mac is not None:
            with open(os.path.join(batch,
                                   name + cc_fleet.MAC_SUFFIX),
                      "wb") as f:
                f.write(mac.encode())
    return batch


def test_absorb_verifies_and_flattens(tmp_path, monkeypatch):
    monkeypatch.setenv(cc_fleet.SECRET_ENV, "test-secret")
    secret = cc_fleet.shared_secret(None)
    base = str(tmp_path)
    good = store.pack_entry({"site": "t"}, b"p")
    other = store.pack_entry({"site": "t"}, b"q")
    batch = _mint_batch(base, [
        ("c" * 40 + store.SUFFIX, good, cc_fleet.entry_mac(secret,
                                                           good)),
        ("d" * 40 + store.SUFFIX, b"corrupt",
         cc_fleet.entry_mac(secret, b"corrupt")),
        ("e" * 40 + store.SUFFIX, other, "0" * 64),  # forged MAC
        ("f" * 40 + store.SUFFIX, other, None),      # no sidecar
        ("notes.txt", b"skip me", None),
    ])
    n = cc_fleet.absorb(base, "compilecache/cc-test")
    assert n == 1
    assert not os.path.exists(batch), "batch dir must be removed"
    flat = os.path.join(base, "compilecache")
    assert [e["name"] for e in store.entries(flat)] == \
        ["c" * 40 + store.SUFFIX]


def test_transfers_refuse_without_secret(tmp_path, monkeypatch):
    """The RCE guard: no shared secret means NO network bytes are ever
    unpickled — absorb drops the whole batch, pull and push refuse
    outright.  The local cache is untouched either way."""
    monkeypatch.delenv(cc_fleet.SECRET_ENV, raising=False)
    base = str(tmp_path)
    good = store.pack_entry({"site": "t"}, b"p")
    batch = _mint_batch(base, [
        ("c" * 40 + store.SUFFIX, good, None)])
    assert cc_fleet.shared_secret(base) is None
    # a FILE at <base>/fleet makes the coordinator's auto-mint fail,
    # pinning the secretless-absorb branch: the whole batch drops
    with open(os.path.join(base, "fleet"), "wb") as f:
        f.write(b"not a dir")
    assert cc_fleet.absorb(base, "compilecache/cc-test") == 0
    assert not os.path.exists(batch)
    assert store.entries(os.path.join(base, "compilecache")) == []
    # with a mintable secret, an entry missing its MAC sidecar is
    # still dropped — unauthenticated bytes are never unpickled
    os.remove(os.path.join(base, "fleet"))
    batch = _mint_batch(base, [
        ("c" * 40 + store.SUFFIX, good, None)])
    assert cc_fleet.absorb(base, "compilecache/cc-test") == 0
    assert store.entries(os.path.join(base, "compilecache")) == []
    # worker side: no secret -> pull refuses before any HTTP
    adv = [{"name": "c" * 40 + store.SUFFIX, "digest": "0" * 64,
            "size": 1}]
    d = os.path.join(base, "wdir")
    assert cc_fleet.pull_missing("http://127.0.0.1:9", adv, d,
                                 secret=None) == 0
    assert cc_fleet.push_new(object(), {"x" + store.SUFFIX}, d,
                             secret=None) is False


def test_shared_secret_mint_and_reuse(tmp_path, monkeypatch):
    monkeypatch.delenv(cc_fleet.SECRET_ENV, raising=False)
    base = str(tmp_path)
    assert cc_fleet.shared_secret(base) is None, "no mint on read"
    s = cc_fleet.shared_secret(base, create=True)
    assert s and len(s) == 64  # token_hex(32)
    assert cc_fleet.shared_secret(base) == s, "stable across reads"
    assert os.stat(os.path.join(base, "fleet", "secret")).st_mode \
        & 0o777 == 0o600
    monkeypatch.setenv(cc_fleet.SECRET_ENV, "env-wins")
    assert cc_fleet.shared_secret(base) == b"env-wins"


def test_fleet_prewarmed_first_claim_zero_miss(tmp_path, monkeypatch):
    """End to end over a real coordinator + HTTP server: the claim
    adverts the coordinator's entries, the worker pulls what it lacks
    (HMAC-verified under the shared secret), and its FIRST dispatch of
    those classes counts ZERO misses.  Wrong digests are rejected; a
    worker-minted entry pushed over the artifact channel (with MAC
    sidecars) lands in the coordinator's flat store."""
    from jepsen_tpu import web
    from jepsen_tpu.fleet import FleetCoordinator, FleetWorker

    # the coordinator and (different-base) worker share the fleet
    # secret the multi-host way: the env var
    monkeypatch.setenv(cc_fleet.SECRET_ENV, "fleet-test-secret")
    secret = cc_fleet.shared_secret(None)
    base1 = str(tmp_path / "coord")
    cdir = os.path.join(base1, "compilecache")
    compilecache.set_cache_dir(cdir)
    f = _jit_double()
    xs = [_arange(64), _arange(128)]
    for x in xs:
        compilecache.call("t.fleet", f, x)
    names = cc_fleet.entry_names(cdir)
    assert len(names) == 2

    spec = {"name": "cc", "workloads": ["set"], "seeds": [1],
            "opts": {"time-limit": 0.1}}
    coord = FleetCoordinator(spec, base1, lease_s=5.0)
    srv = web.serve(port=0, base=base1, background=True, fleet=coord)
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        code, resp = coord.claim({"worker": "w1"})
        assert code == 200 and resp.get("spec") is not None
        adv = resp.get("compilecache")
        assert adv and {r["name"] for r in adv} == names

        # worker side: a fresh store pulls everything at claim time
        base2 = str(tmp_path / "worker")
        wdir = os.path.join(base2, "compilecache")
        compilecache.set_cache_dir(wdir)
        assert cc_fleet.pull_missing(url, adv, wdir, secret) == 2
        assert cc_fleet.pull_missing(url, adv, wdir,
                                     secret) == 0  # idempotent
        compilecache.clear()
        compilecache.reset_stats()
        for x in xs:
            out = compilecache.call("t.fleet", f, x)
            assert np.array_equal(np.asarray(out),
                                  np.asarray(x) * 2 + 1)
        st = compilecache.stats()
        assert st["misses"] == 0 and st["fallthroughs"] == 0, st
        assert st["hits"] == 2

        # a wrong-digest advert is rejected, never installed
        victim = sorted(names)[0]
        os.remove(os.path.join(wdir, victim))
        bad = [{"name": victim, "digest": "0" * 64, "size": 1}]
        assert cc_fleet.pull_missing(url, bad, wdir, secret) == 0
        assert victim not in cc_fleet.entry_names(wdir)

        # a wrong SECRET fails the MAC check before anything else
        good_adv = [r for r in adv if r["name"] == victim]
        assert cc_fleet.pull_missing(url, good_adv, wdir,
                                     b"wrong-secret") == 0
        assert victim not in cc_fleet.entry_names(wdir)

        # push: a worker-minted class travels back and is absorbed
        x256 = _arange(256)
        compilecache.call("t.fleet", f, x256)
        new = cc_fleet.entry_names(wdir) - names
        assert len(new) == 1
        w = FleetWorker(url, base2, name="w1", poll_s=0.05)
        assert cc_fleet.push_new(w, new, wdir, secret)
        assert new <= cc_fleet.entry_names(cdir)
    finally:
        srv.server_close()
        coord.close()
