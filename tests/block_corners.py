"""Block-structure corner systems: the shapes the chip path meets.

Each builder returns a small PageRank system (d = 0.85) whose structure
sits on a corner of the block-sparse (BSR) machinery at ``bs = 16``: a
partial last tile row, only diagonal tiles, whole hosts in popularity
order, one seeded page, a host that never sends or never receives
fluid, a full tile row, one hot column.  Pages are grouped into hosts
of ``HOST`` consecutive ids, so with ``bs == HOST`` a host is one tile
row.  At these sizes the engine packs six real buckets of ceil(N / 6)
pages, so its last bucket is partial wherever 6 does not divide N.

Shared by ``test_backend_corners.py`` (parity against an independent
dense reference on every backend, and op-accounting parity) and
``test_invariants.py`` (fluid conservation on the session backends).
"""
import numpy as np

import repro
from repro.core import host_block_graph, pagerank_system
from repro.core.graph import CSRGraph

DAMPING = 0.85
HOST = 16


def _system(g: CSRGraph, b=None):
    """PageRank (P, B) of ``g``; ``b`` overrides the uniform teleport."""
    p, b_uni = pagerank_system(g, damping=DAMPING)
    return repro.Problem.linear(p, b_uni if b is None else b,
                                eps=1.0 - DAMPING, target_error=1e-5)


def _crawl(n: int, seed: int, **kw) -> CSRGraph:
    return host_block_graph(n, host_size=HOST, links_per_node=4.0,
                            seed=seed, **kw)


def _edges(g: CSRGraph):
    src, dst, _ = g.edge_list()
    return src.astype(np.int64), dst.astype(np.int64)


def _graph(src, dst, n: int) -> CSRGraph:
    """Unit-weight link graph without self-loops or duplicate links."""
    key = np.unique(src[src != dst] * n + dst[src != dst])
    return CSRGraph.from_edges((key // n).astype(np.int32),
                               (key % n).astype(np.int32),
                               np.ones(key.shape[0]), n)


def tile_tail():
    """N = 16·5 + 7: the last BSR block row and the last engine bucket
    are partial."""
    n = HOST * 5 + 7
    g = _crawl(n, seed=11)
    assert n % HOST and g.n == n
    return _system(g)


def block_diagonal():
    """Every link stays inside its own host: only diagonal tiles exist,
    so only diagonal tiles are ever armed."""
    n = HOST * 6
    g = _crawl(n, seed=12, intra_frac=1.0)
    src, dst = _edges(g)
    assert (src // HOST == dst // HOST).all()
    return _system(g)


def popsort_relabel(g: CSRGraph, seed: int) -> CSRGraph:
    """Whole hosts in descending order of their out-links (ties drawn
    from ``seed``), pages in an order drawn from ``seed`` within each
    host: the popularity order of the ``webhost-popsort`` deployment."""
    n = g.n
    n_hosts = n // HOST
    rng = np.random.default_rng(seed)
    host_links = g.out_degree().reshape(n_hosts, HOST).sum(axis=1)
    order = np.lexsort((rng.permutation(n_hosts), -host_links))
    new_host = np.empty(n_hosts, dtype=np.int64)
    new_host[order] = np.arange(n_hosts)  # new host i = old host order[i]
    slot = rng.permuted(np.tile(np.arange(HOST), (n_hosts, 1)), axis=1)
    new_of_old = (new_host[:, None] * HOST + slot).reshape(-1)
    src, dst = _edges(g)
    return _graph(new_of_old[src], new_of_old[dst], n)


def popsort_hosts():
    """A host-ordered crawl relabelled with hosts sorted by out-links:
    the busiest host rows come first and the tiles scatter off the
    diagonal band."""
    n = HOST * 10
    g = popsort_relabel(_crawl(n, seed=13), seed=13)
    host_links = g.out_degree().reshape(-1, HOST).sum(axis=1)
    assert (np.diff(host_links) <= 0).all()
    return _system(g)


def one_seed():
    """B on a single page (a personalized-PageRank query): the first
    rounds arm one tile row."""
    n = HOST * 8
    g = _crawl(n, seed=14)
    b = np.zeros(n)
    b[HOST * 3 + 5] = 1.0 - DAMPING
    return _system(g, b)


def dangling_host():
    """One whole host has no out-links: its tile row is empty and all of
    its fluid leaves as dangling mass."""
    n = HOST * 8
    src, dst = _edges(_crawl(n, seed=15))
    keep = src // HOST != 3
    g = _graph(src[keep], dst[keep], n)
    assert (g.out_degree()[HOST * 3:HOST * 4] == 0).all()
    return _system(g)


def unreached_block():
    """One host has no in-links and zero B: its tiles are never armed and
    its pages end at zero."""
    n = HOST * 8
    src, dst = _edges(_crawl(n, seed=16))
    cold = 5
    g = _graph(src[dst // HOST != cold], dst[dst // HOST != cold], n)
    b = np.full(n, (1.0 - DAMPING) / n)
    b[HOST * cold:HOST * (cold + 1)] = 0.0
    assert (g.in_degree()[HOST * cold:HOST * (cold + 1)] == 0).all()
    return _system(g, b)


def hub_out():
    """One page links to every other page: a full tile row."""
    n = HOST * 8
    src, dst = _edges(_crawl(n, seed=17))
    hub = HOST * 2 + 1
    keep = src != hub
    src = np.concatenate([src[keep], np.full(n - 1, hub)])
    dst = np.concatenate([dst[keep], np.delete(np.arange(n), hub)])
    g = _graph(src, dst, n)
    assert g.out_degree()[hub] == n - 1
    return _system(g)


def hub_in():
    """Every page links to one page: one hot column."""
    n = HOST * 8
    src, dst = _edges(_crawl(n, seed=18))
    hub = HOST * 6 + 9
    src = np.concatenate([src, np.delete(np.arange(n), hub)])
    dst = np.concatenate([dst, np.full(n - 1, hub)])
    g = _graph(src, dst, n)
    assert g.in_degree()[hub] == n - 1
    return _system(g)


BLOCK_CORNERS = {
    "tile_tail": tile_tail,
    "block_diagonal": block_diagonal,
    "popsort_hosts": popsort_hosts,
    "one_seed": one_seed,
    "dangling_host": dangling_host,
    "unreached_block": unreached_block,
    "hub_out": hub_out,
    "hub_in": hub_in,
}
