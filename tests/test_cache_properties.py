"""Property tests for the per-session LRU plan/executable/calibration caches.

Each property runs under ``hypothesis`` when it is installed and falls back
to deterministic seeded cases otherwise (tier-1 images without hypothesis
still get coverage).  The LRU model check drives the real ``_lru_get`` /
``_lru_put`` primitives against a reference implementation; the rest
exercise the public :class:`repro.core.Session` surface (signature
invalidation on ``add``, ``clear_caches`` zeroing ``cache_stats``,
calibration keying/eviction via ``SessionConfig.cache_size``).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OpGraph, OpKind, Session, SessionConfig
from repro.core import calibration_key, graph_signature
from repro.core.session import _lru_get, _lru_put
from repro.core.profiler import ProfileTable

from conftest import build_inception_like

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic fallback below
    HAVE_HYPOTHESIS = False


# -- LRU model check -----------------------------------------------------------

def _reference_lru(ops, capacity):
    """Reference semantics: dict + recency list, evict least-recent on put."""
    store, recency = {}, []

    def touch(k):
        if k in recency:
            recency.remove(k)
        recency.append(k)

    out = []
    for op, key, val in ops:
        if op == "put":
            store[key] = val
            touch(key)
            while len(store) > capacity:
                victim = recency.pop(0)
                del store[victim]
        else:
            if key in store:
                touch(key)
                out.append(store[key])
            else:
                out.append(None)
    return store, out


def _check_lru_matches_model(ops, capacity):
    from collections import OrderedDict
    cache = OrderedDict()
    got = []
    for op, key, val in ops:
        if op == "put":
            _lru_put(cache, key, val, max_entries=capacity)
        else:
            got.append(_lru_get(cache, key))
    ref_store, ref_gets = _reference_lru(ops, capacity)
    assert dict(cache) == ref_store
    assert got == ref_gets
    assert len(cache) <= capacity


def _ops_from_seed(seed, n=60, n_keys=8):
    rng = np.random.default_rng(seed)
    return [("put" if rng.random() < 0.5 else "get",
             (int(rng.integers(n_keys)),), int(rng.integers(1000)))
            for _ in range(n)]


if HAVE_HYPOTHESIS:
    op_strategy = st.lists(
        st.tuples(st.sampled_from(["put", "get"]),
                  st.tuples(st.integers(0, 7)), st.integers(0, 999)),
        max_size=80)

    @settings(max_examples=60, deadline=None)
    @given(ops=op_strategy, capacity=st.integers(1, 6))
    def test_lru_matches_reference_model(ops, capacity):
        _check_lru_matches_model(ops, capacity)
else:
    @pytest.mark.parametrize("seed", range(30))
    def test_lru_matches_reference_model(seed):
        _check_lru_matches_model(_ops_from_seed(seed), 1 + seed % 6)


def test_lru_hit_after_put_and_eviction_order():
    """Explicit sanity on top of the model check: hit-after-put, LRU victim
    selection, and get-refreshes-recency."""
    from collections import OrderedDict
    c = OrderedDict()
    _lru_put(c, ("a",), 1, max_entries=2)
    assert _lru_get(c, ("a",)) == 1          # hit after put
    _lru_put(c, ("b",), 2, max_entries=2)
    assert _lru_get(c, ("a",)) == 1          # refresh "a"
    _lru_put(c, ("c",), 3, max_entries=2)    # evicts "b" (LRU), not "a"
    assert _lru_get(c, ("b",)) is None
    assert _lru_get(c, ("a",)) == 1
    assert _lru_get(c, ("c",)) == 3


# -- signature invalidation / stats --------------------------------------------

def _check_add_invalidates(seed):
    sess = Session()
    g = build_inception_like(n_blocks=1 + seed % 3, width=2 + seed % 3,
                             with_payloads=False, seed=seed)
    sig1 = graph_signature(g)
    sess.plan(g)
    assert sess.cache_stats()["plan_misses"] >= 1
    g.add(f"extra{seed}", OpKind.ELEMENTWISE, [0])
    assert graph_signature(g) != sig1
    before_hits = sess.cache_stats()["plan_hits"]
    sess.plan(g)  # must NOT hit the stale pre-mutation entry
    assert sess.cache_stats()["plan_hits"] == before_hits


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_add_invalidates_signature_and_plan_cache(seed):
        _check_add_invalidates(seed)
else:
    @pytest.mark.parametrize("seed", range(10))
    def test_add_invalidates_signature_and_plan_cache(seed):
        _check_add_invalidates(seed)


def test_add_drops_hydrated_calibration():
    sess = Session()
    g = build_inception_like(n_blocks=2, width=2)
    inputs = {0: jnp.ones((8, 64), jnp.float32)}
    sess.calibrate(g, inputs, repeats=1)
    assert g.calibration_fp is not None
    g.add("extra", OpKind.ELEMENTWISE, [0])
    assert g.calibration_fp is None
    assert all(n.cost.measured_us is None for n in g)


def test_clear_caches_zeroes_stats_and_entries():
    sess = Session()
    g = build_inception_like(n_blocks=2, width=2)
    sess.plan(g)
    sess.optimize(g)
    sess.calibrate(g, {0: jnp.ones((8, 64), jnp.float32)}, repeats=1)
    stats = sess.cache_stats()
    assert stats["plan_misses"] and stats["exec_misses"] \
        and stats["calib_misses"]
    sess.clear_caches()
    assert all(v == 0 for v in sess.cache_stats().values())


# -- calibration-cache keying --------------------------------------------------

def test_calibration_key_distinguishes_input_geometry():
    g = OpGraph("g")
    a = g.add("x", OpKind.INPUT)
    g.add("y", OpKind.ELEMENTWISE, [a], fn=lambda v: v * 2)
    k1 = calibration_key(g, {a: jnp.ones((4, 8), jnp.float32)})
    k2 = calibration_key(g, {a: jnp.ones((8, 8), jnp.float32)})
    k3 = calibration_key(g, {a: jnp.ones((4, 8), jnp.bfloat16)})
    assert len({k1, k2, k3}) == 3
    # same geometry, different values → same key (timings are value-blind)
    k4 = calibration_key(g, {a: jnp.zeros((4, 8), jnp.float32)})
    assert k4 == k1


def test_cpu_timing_never_hydrates_a_tpu_plan(tmp_path, monkeypatch):
    """Calibration is keyed by the device that timed it: a table measured
    on the CPU is a miss, on both tiers, for a process on a TPU."""
    import repro.core.session as session_mod

    g = build_inception_like(n_blocks=1, width=2)
    inputs = {0: jnp.ones((8, 64), jnp.float32)}
    calib_dir = str(tmp_path / "calib")
    table = Session(calib_dir=calib_dir).calibrate(g, inputs, repeats=1)
    assert table.device == "cpu:cpu"
    monkeypatch.setattr(session_mod, "measuring_device",
                        lambda: "tpu:TPU v5 lite")
    tpu = Session(calib_dir=calib_dir)
    tpu.calibrate(g, inputs, repeats=1)
    stats = tpu.cache_stats()
    assert stats["calib_disk_hits"] == 0 and stats["calib_misses"] == 1
    assert (calibration_key(g, inputs, "cpu:cpu")
            != calibration_key(g, inputs, "tpu:TPU v5 lite"))


def test_calibration_cache_evicts_lru():
    sess = Session(SessionConfig(cache_size=2))
    g = build_inception_like(n_blocks=1, width=2)
    shapes = [(4, 64), (8, 64), (16, 64)]
    for s in shapes:
        sess.calibrate(g, {0: jnp.ones(s, jnp.float32)}, repeats=1)
    assert sess.cache_stats()["calib_entries"] == 2
    # oldest geometry was evicted → re-calibrating it misses the memory LRU
    # (load=False pins the check to the in-memory tier; with the disk tier
    # enabled the eviction would instead resolve as a calib_disk_hit)
    misses = sess.cache_stats()["calib_misses"]
    sess.calibrate(g, {0: jnp.ones(shapes[0], jnp.float32)}, repeats=1,
                   load=False)
    assert sess.cache_stats()["calib_misses"] == misses + 1
    # most-recent geometry is still warm
    hits = sess.cache_stats()["calib_hits"]
    sess.calibrate(g, {0: jnp.ones(shapes[2], jnp.float32)}, repeats=1)
    assert sess.cache_stats()["calib_hits"] == hits + 1


def test_profile_table_is_detachable_and_reappliable():
    from repro.core import apply_profile, detach_profile
    sess = Session()
    g = build_inception_like(n_blocks=1, width=2)
    sess.calibrate(g, {0: jnp.ones((8, 64), jnp.float32)}, repeats=1)
    table = detach_profile(g)
    assert isinstance(table, ProfileTable)
    assert g.calibration_fp is None
    assert all(n.cost.measured_us is None for n in g)
    apply_profile(g, table)
    assert g.calibration_fp == table.fingerprint
    assert dict(table.measured_us) == {
        n.op_id: n.cost.measured_us for n in g
        if n.cost.measured_us is not None}
