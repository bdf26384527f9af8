"""Pallas kernel validation: shape/dtype sweeps vs the jnp oracle
(off the TPU the platform selects interpret mode, which executes the kernel
body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref


def _random_problem(rng, e, d, v, sorted_=True):
    seg = rng.integers(0, v, e).astype(np.int32)
    if sorted_:
        seg = np.sort(seg)
    vals = rng.normal(size=(e, d)).astype(np.float32) if d else \
        rng.normal(size=(e,)).astype(np.float32)
    return jnp.asarray(vals), jnp.asarray(seg)


@pytest.mark.parametrize("e,d,v", [
    (64, 0, 16),        # 1-D values, tiny
    (1000, 33, 300),    # unaligned feature dim
    (512, 128, 256),    # exactly tile-aligned
    (2048, 16, 1000),   # many segments
    (513, 7, 100),      # off-by-one edge count
    (100, 200, 50),     # d > E_TILE lanes-worth
    (5000, 0, 3000),    # many edge tiles and vertex blocks
])
def test_segment_sum_shapes(e, d, v):
    rng = np.random.default_rng(e * 7 + d)
    vals, seg = _random_problem(rng, e, d, v)
    out = ops.segment_sum(vals, seg, num_segments=v)
    exp = ref.segment_sum_ref(vals, seg, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_segment_sum_dtypes(dtype):
    rng = np.random.default_rng(5)
    seg = np.sort(rng.integers(0, 64, 500)).astype(np.int32)
    if dtype == jnp.int32:
        vals = jnp.asarray(rng.integers(0, 3, (500, 8)), dtype)
    else:
        vals = jnp.asarray(rng.normal(size=(500, 8)), dtype)
    out = ops.segment_sum(vals, jnp.asarray(seg), num_segments=64)
    exp = ref.segment_sum_ref(vals, jnp.asarray(seg), 64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=tol, atol=tol)


def test_segment_sum_sentinel_padding():
    """ids >= num_segments must contribute nothing (graph padding)."""
    seg = jnp.asarray(np.array([0, 1, 1, 7, 8, 100], np.int32))
    vals = jnp.ones((6,), jnp.float32)
    out = ops.segment_sum(vals, seg, num_segments=7)
    assert float(out.sum()) == 3.0  # ids 7, 8, 100 dropped


def test_segment_sum_unsorted():
    rng = np.random.default_rng(9)
    vals, seg = _random_problem(rng, 777, 12, 99, sorted_=False)
    out = ops.segment_sum(vals, seg, num_segments=99, presorted=False)
    exp = ref.segment_sum_ref(vals, seg, 99)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 600), st.integers(1, 40),
       st.integers(1, 120))
def test_segment_sum_property(seed, e, d, v):
    rng = np.random.default_rng(seed)
    vals, seg = _random_problem(rng, e, d, v)
    out = ops.segment_sum(vals, seg, num_segments=v)
    exp = ref.segment_sum_ref(vals, seg, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)
    # conservation: total mass preserved (all ids < v here)
    np.testing.assert_allclose(float(out.sum()), float(vals.sum()),
                               rtol=1e-4, atol=1e-3)


def test_peel_update_vs_ref(er_graph):
    g = er_graph
    rng = np.random.default_rng(1)
    src_s, dst_s = g.dst_sorted()
    failed = jnp.asarray(rng.random(g.n_nodes) < 0.3)
    out = ops.peel_update(jnp.asarray(src_s), jnp.asarray(dst_s), failed,
                          n_nodes=g.n_nodes)
    exp = ref.peel_update_ref(jnp.asarray(g.src), jnp.asarray(g.dst), failed,
                              g.n_nodes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp))


def test_peel_update_matches_pass_semantics(er_graph):
    """The kernel IS the paper's part-2: deg' = deg - delta reproduces one
    P-Bahmani pass on live vertices."""
    g = er_graph
    deg = g.degrees().astype(np.int64)
    rho = g.n_edges / g.n_nodes
    failed = deg <= 2 * rho
    src_s, dst_s = g.dst_sorted()
    delta = np.asarray(ops.peel_update(
        jnp.asarray(src_s), jnp.asarray(dst_s), jnp.asarray(failed),
        n_nodes=g.n_nodes))
    s, d = g.src[:g.n_directed], g.dst[:g.n_directed]
    expected = np.bincount(d[failed[s]], minlength=g.n_nodes)
    np.testing.assert_array_equal(delta.astype(np.int64), expected)


def test_peel_update_returns_int32(er_graph):
    """The peel recurrence is int32; the f32 MXU accumulator must cast at
    the op boundary (ISSUE 7 satellite — the silent upcast broke kernel-path
    bit-identity with the scatter tier)."""
    g = er_graph
    src_s, dst_s = g.dst_sorted()
    failed = jnp.zeros(g.n_nodes, bool).at[::3].set(True)
    out = ops.peel_update(jnp.asarray(src_s), jnp.asarray(dst_s), failed,
                          n_nodes=g.n_nodes)
    assert out.dtype == jnp.int32
    xla = ops.peel_update(jnp.asarray(src_s), jnp.asarray(dst_s), failed,
                          n_nodes=g.n_nodes, impl="xla")
    assert xla.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(xla))


def test_segment_sum_all_sentinel():
    """Every id out of range (a fully-padded bucket tail): exact zeros."""
    seg = jnp.full((700,), 1 << 20, jnp.int32)
    vals = jnp.ones((700,), jnp.float32)
    out = ops.segment_sum(vals, seg, num_segments=32)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(32, np.float32))


def test_segment_sum_one_segment_straddles_tiles():
    """A single hot segment wider than E_TILE (duplicate ids crossing every
    tile boundary) must accumulate across the whole sequential grid."""
    e = 1537  # 3 full 512-lane tiles + 1
    seg = jnp.zeros((e,), jnp.int32)
    vals = jnp.ones((e,), jnp.float32)
    out = ops.segment_sum(vals, seg, num_segments=4)
    np.testing.assert_array_equal(
        np.asarray(out), np.array([e, 0, 0, 0], np.float32))


def test_segment_sum_duplicates_at_tile_boundary():
    """Segments deliberately split across the 512-lane tile edge."""
    seg_np = np.sort(np.r_[np.full(510, 3), np.full(5, 4), np.full(509, 5)])
    seg = jnp.asarray(seg_np.astype(np.int32))
    vals = jnp.ones((seg_np.size,), jnp.float32)
    out = np.asarray(ops.segment_sum(vals, seg, num_segments=8))
    np.testing.assert_array_equal(
        out, np.bincount(seg_np, minlength=8).astype(np.float32))


def test_unsorted_fallback_emits_obs_counter():
    """presorted=False argsorts inside the compiled program; the obs counter
    is how a deployment notices a hot path quietly re-sorting (ISSUE 7)."""
    from repro.obs.trace import Tracer, set_tracer

    tr = Tracer(profiler_bridge=False)
    prev = set_tracer(tr)
    try:
        rng = np.random.default_rng(11)
        vals, seg = _random_problem(rng, 300, 4, 50, sorted_=False)
        ops.segment_sum(vals, seg, num_segments=50, presorted=False)
        ops.segment_sum(vals, seg, num_segments=50, presorted=False)
        assert tr.registry.counter(
            "kernel_unsorted_fallback_total", op="segment_sum").value == 2
        # the sorted path must NOT touch the counter
        vals_s, seg_s = _random_problem(rng, 300, 4, 50, sorted_=True)
        ops.segment_sum(vals_s, seg_s, num_segments=50)
        assert tr.registry.counter(
            "kernel_unsorted_fallback_total", op="segment_sum").value == 2
    finally:
        set_tracer(prev)


@pytest.mark.parametrize("n,d,e,v,weighted", [
    (50, 16, 1000, 300, True),
    (20, 64, 200, 64, False),
    (100, 8, 64, 8, True),
])
def test_segment_embed(n, d, e, v, weighted):
    rng = np.random.default_rng(n + e)
    table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    gid = jnp.asarray(rng.integers(0, n, e).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, v, e)).astype(np.int32))
    w = jnp.asarray(rng.random(e).astype(np.float32)) if weighted else None
    out = ops.segment_embed(table, gid, seg, w, num_segments=v)
    exp = ref.segment_embed_ref(table, gid, seg, w, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# prefix sum + stream compaction (ISSUE 7: device-resident bucket compaction)
# ---------------------------------------------------------------------------
from repro.kernels.compact import P_TILE, prefix_sum, stream_compact


def _compact_oracle(values: np.ndarray, live: np.ndarray, out_size: int,
                    fill: int) -> np.ndarray:
    """The scatter it replaces: full(fill).at[cumsum-1].set(mode="drop")."""
    out = np.full((out_size,) + values.shape[1:], fill, np.int32)
    pos = np.cumsum(live.astype(np.int64)) - 1
    for i in range(values.shape[0]):
        if live[i] and 0 <= pos[i] < out_size:
            out[pos[i]] = values[i]
    return out


@pytest.mark.parametrize("e", [1, 7, P_TILE - 1, P_TILE, P_TILE + 1, 1500,
                               5000])
def test_prefix_sum_matches_numpy(e):
    rng = np.random.default_rng(e)
    x = rng.integers(0, 4, e).astype(np.int32)
    out = prefix_sum(jnp.asarray(x))
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.cumsum(x))


def test_prefix_sum_bool_and_extremes():
    ones = jnp.ones((3 * P_TILE + 5,), bool)
    np.testing.assert_array_equal(
        np.asarray(prefix_sum(ones)), np.arange(1, 3 * P_TILE + 6))
    zeros = jnp.zeros((P_TILE + 1,), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(prefix_sum(zeros)), np.zeros(P_TILE + 1, np.int32))


@pytest.mark.parametrize("e,out_size,p_live", [
    (100, 128, 0.5),
    (1500, 1024, 0.7),
    (513, 512, 0.3),
    (64, 16, 0.9),     # overflow: survivors > out_size must drop, not wrap
    (5000, 4096, 0.6),  # many scan tiles
])
def test_stream_compact_matches_scatter(e, out_size, p_live):
    rng = np.random.default_rng(e + out_size)
    values = rng.integers(0, 10_000, e).astype(np.int32)
    live = rng.random(e) < p_live
    out = stream_compact(jnp.asarray(values), jnp.asarray(live),
                         out_size=out_size, fill=out_size)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(out), _compact_oracle(values, live, out_size, out_size))


def test_stream_compact_2d_and_order():
    """2-D payloads (remapped src/dst pairs) compact row-wise, and the
    survivor order is the lane order — the sortedness invariant the pruned
    kernel path relies on (a dst-sorted parent stays dst-sorted)."""
    rng = np.random.default_rng(3)
    e, out_size = 400, 256
    dst = np.sort(rng.integers(0, 40, e)).astype(np.int32)
    src = rng.integers(0, 40, e).astype(np.int32)
    live = rng.random(e) < 0.6
    packed = np.asarray(stream_compact(
        jnp.asarray(np.stack([src, dst], axis=1)), jnp.asarray(live),
        out_size=out_size, fill=out_size))
    k = int(live.sum())
    np.testing.assert_array_equal(packed[:k, 0], src[live])
    np.testing.assert_array_equal(packed[:k, 1], dst[live])
    assert (np.diff(packed[:k, 1]) >= 0).all()  # still dst-sorted
    assert (packed[k:] == out_size).all()       # sentinel tail


def test_stream_compact_all_dead_all_live():
    vals = jnp.arange(300, dtype=jnp.int32)
    dead = stream_compact(vals, jnp.zeros(300, bool), out_size=64, fill=-7)
    np.testing.assert_array_equal(np.asarray(dead), np.full(64, -7))
    alive = stream_compact(vals, jnp.ones(300, bool), out_size=512, fill=512)
    np.testing.assert_array_equal(
        np.asarray(alive), np.r_[np.arange(300), np.full(212, 512)])


# ---------------------------------------------------------------------------
# the platform, not a flag, picks interpret mode and the kernel default
# ---------------------------------------------------------------------------
def test_platform_selects_interpret_mode():
    """Off the TPU the kernels run interpreted: the program holds no
    compiled Pallas call, and kernel=True still answers exactly."""
    from repro.kernels.segsum import interpret_default, segment_sum_sorted

    assert jax.default_backend() != "tpu"
    assert interpret_default()
    lowered = jax.jit(lambda v, s: segment_sum_sorted(
        v, s, num_segments=8)).lower(jnp.ones(600), jnp.zeros(600, jnp.int32))
    assert "tpu_custom_call" not in lowered.as_text()


@pytest.mark.parametrize("kernel,expected", [(None, False), (False, False),
                                             (True, True)])
def test_kernel_knob_resolution(kernel, expected):
    """kernel=None is the scatter tier on every platform."""
    from repro.stream import DeltaEngine, GraphRegistry

    assert DeltaEngine(n_nodes=8, kernel=kernel).kernel is expected
    assert GraphRegistry(kernel=kernel).register("t", 8).kernel is expected
