"""Density primitives + exact solver vs brute force (paper Definition 1/3)."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import exact_densest, check_approx_bound, subgraph_density
from repro.core.density import induced_edge_count, masked_degrees
from repro.graphs.graph import Graph


def brute_force_densest(g: Graph) -> float:
    """Enumerate all vertex subsets (n <= 12)."""
    n = g.n_nodes
    best = 0.0
    for r in range(1, n + 1):
        for sub in itertools.combinations(range(n), r):
            mask = np.zeros(n, bool)
            mask[list(sub)] = True
            best = max(best, g.subgraph_density(mask))
    return best


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(4, 9), st.integers(20, 60))
def test_exact_matches_brute_force(seed, n, pct):
    rng = np.random.default_rng(seed)
    iu = np.array(list(itertools.combinations(range(n), 2)))
    keep = rng.random(iu.shape[0]) < pct / 100
    if keep.sum() == 0:
        return
    g = Graph.from_edges(iu[keep], n_nodes=n)
    rho_exact, mask = exact_densest(g)
    rho_bf = brute_force_densest(g)
    assert abs(rho_exact - rho_bf) < 1e-6
    assert abs(g.subgraph_density(mask) - rho_bf) < 1e-6  # mask is optimal


def test_density_device_vs_host(er_graph):
    g = er_graph
    rng = np.random.default_rng(3)
    mask = rng.random(g.n_nodes) < 0.5
    dev = float(subgraph_density(jnp.asarray(g.src), jnp.asarray(g.dst),
                                 jnp.asarray(mask), g.n_nodes))
    assert abs(dev - g.subgraph_density(mask)) < 1e-5


def test_masked_degrees(er_graph):
    g = er_graph
    mask = np.ones(g.n_nodes, bool)
    deg = np.asarray(masked_degrees(jnp.asarray(g.src), jnp.asarray(g.dst),
                                    jnp.asarray(mask), g.n_nodes))
    assert np.array_equal(deg, g.degrees())


def test_induced_edge_count(er_graph):
    g = er_graph
    mask = np.zeros(g.n_nodes, bool)
    mask[:200] = True
    ne = int(induced_edge_count(jnp.asarray(g.src), jnp.asarray(g.dst),
                                jnp.asarray(mask), g.n_nodes))
    s, d = g.src[:g.n_directed], g.dst[:g.n_directed]
    assert ne == int((mask[s] & mask[d]).sum()) // 2


def test_approx_bound_helper():
    assert check_approx_bound(5.0, 10.0, 2.0)
    assert not check_approx_bound(4.9, 10.0, 2.0)


def test_known_exact_densities(named_graph):
    rho, mask = exact_densest(named_graph)
    assert rho == pytest.approx(brute_force_densest(named_graph), abs=1e-9)


@pytest.mark.parametrize("case", ["counts", "wide_numerator", "exact",
                                  "halves", "edges"])
def test_ratio_is_the_ieee_quotient(case):
    """``ratio`` must equal numpy's correctly rounded f32 quotient bit for
    bit (the TPU's own f32 division can land one ulp off)."""
    from repro.core.density import ratio

    rng = np.random.default_rng(7)
    n = 200_000
    if case == "counts":
        num, den = rng.integers(0, 1 << 24, n), rng.integers(0, 1 << 24, n)
    elif case == "wide_numerator":  # refine load sums pass 2^24
        num, den = rng.integers(0, 2**31 - 1, n), rng.integers(1, 1 << 24, n)
    elif case == "exact":           # ties deg == 2 n_e / n_v
        den = rng.integers(1, 1 << 20, n)
        num = den * rng.integers(0, 16, n)
    elif case == "halves":          # quotients on a half-integer
        den = 2 * rng.integers(1, 1 << 12, n)
        num = den // 2 * (2 * rng.integers(0, 1 << 11, n) + 1)
    else:
        num = np.array([3631, 0, 1, (1 << 24) - 1, 2**31 - 1, 5, 1 << 24])
        den = np.array([190, 0, (1 << 24) - 1, 1, 3, 0, 3])
    got = np.asarray(ratio(jnp.asarray(num, jnp.int32),
                           jnp.asarray(den, jnp.int32)))
    want = (num.astype(np.float32)
            / np.maximum(den, 1).astype(np.float32)).astype(np.float32)
    np.testing.assert_array_equal(got, want)
