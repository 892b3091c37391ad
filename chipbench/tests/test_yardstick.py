"""The yardstick: plain reference, work counts, graphs and peaks."""

import numpy as np
import pytest

from chipbench import graphs, reference, work


def small_graphs():
    from repro.graphs.gen import (erdos_renyi_edges, ring_of_cliques_edges,
                                  rmat_edges)
    return [ring_of_cliques_edges(5, 6), erdos_renyi_edges(60, 8.0, seed=1),
            rmat_edges(7, 8, seed=3), graphs.kronecker(8, 16, .57, .19, .19, 2)]


@pytest.mark.parametrize("i", range(4))
def test_reference_matches_the_programs_oracles(i):
    from repro.core import truss_wc
    from repro.core.ref import truss_numpy
    from repro.graphs.csr import build_csr

    E = small_graphs()[i]
    rows = graphs.shuffled_rows(E.copy(), np.random.default_rng(i))
    Ec, t = reference.trussness(rows)
    assert np.array_equal(Ec, E)
    assert np.array_equal(t, truss_wc(build_csr(E, int(E.max()) + 1)))
    if E.shape[0] < 600:
        assert np.array_equal(t, truss_numpy(E))


def test_control_breaks_exactness():
    E = graphs.kronecker(8, 16, .57, .19, .19, 0)
    _, exact = reference.trussness(E)
    _, control = reference.trussness(E, cascade=False)
    assert (control != exact).sum() > 0
    assert (control >= exact).all()


def test_kronecker_is_the_programs_rmat():
    from repro.graphs.gen import rmat_edges

    for scale, ef, seed in ((6, 8, 0), (8, 16, 3)):
        assert np.array_equal(graphs.kronecker(scale, ef, .57, .19, .19, seed),
                              rmat_edges(scale, ef, seed=seed))


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11, 2**40 + 3])
def test_work_counts_equal_the_programs_table_sizes(seed):
    from repro.core.support import peel_table_size, support_table_size
    from repro.graphs.csr import (build_csr, canonical_edges_with_rows,
                                  degeneracy_order, relabel)

    base = graphs.kronecker(9, 16, .57, .19, .19, 5)
    rng = np.random.default_rng(seed)
    rows = graphs.shuffled_rows(graphs.relabelling(base, rng)[base], rng)
    E, _, _, n = canonical_edges_with_rows(rows)
    g = build_csr(relabel(E, degeneracy_order(E, n)), n)
    Er = reference.canonical(rows)
    assert work.support_probes(Er) == support_table_size(g)
    assert work.peel_probes(Er) == peel_table_size(g)


def test_relabelling_keeps_the_shapes_the_program_compiles_for():
    base = graphs.kronecker(9, 16, .57, .19, .19, 0)
    shapes = set()
    for seed in range(4):
        rng = np.random.default_rng(seed)
        E = reference.canonical(graphs.relabelling(base, rng)[base])
        shapes.add((E.shape[0], int(E.max()) + 1, work.peel_probes(E)))
    assert len(shapes) == 1


def test_peak_table_refuses_an_unknown_device():
    assert work.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peak("cpu")
