"""kernel.gather_roofline: the engine's tile kernel's share of the chip's
HBM roofline, in %: 100 * the bytes the window's local rounds needed /
(the kernel's device seconds * the chip's HBM bytes/s from
``benchlib.peaks``).

The engine pushes every tile of its pool each local round.  On a
``host_block`` crawl cut into buckets of one host each (``host_size`` pages,
the program's BSR block), a bucket's links reach its own host and the
``span_hosts`` hosts on either side, so a round needs

    (n / host_size) * (2 * span_hosts + 1) tiles * (S^2 + 2 S) * 4 bytes

with S = host_size: each float32 [S, S] tile with the S fluid values it
reads and the S sums it writes.  Hosts at the ends of the crawl have fewer
neighbours, so the count lies at most 0.1% above the tiles the program
builds at the cell's size (bench/tests check it), and below the R * T slots
of the pool's fixed shape, padding included, that the kernel reads every
round: a kernel that moves what it reads reads under 100%.  Its products
are thin (one lane), so bandwidth, not the flop peak, is its roof."""
from benchlib.peaks import PEAKS

PATTERN = r"^bsr_gather_spmm_pallas$"
FLOAT_BYTES = 4


def tiles_per_round(params: dict) -> int:
    """Tiles a local round needs: every host's own tile and one for each
    neighbouring host its links reach."""
    hosts = int(params["n"]) // int(params["host_size"])
    return hosts * (2 * int(params["span_hosts"]) + 1)


def bytes_per_round(params: dict) -> int:
    s = int(params["host_size"])
    return tiles_per_round(params) * (s * s + 2 * s) * FLOAT_BYTES


def roofline(ctx, peak):
    w = ctx.window
    s = ctx.trace.kernel_seconds(PATTERN)
    if peak is None or not s or w.rounds <= 0:
        return None
    need = w.rounds * bytes_per_round(ctx.config["params"])
    return 100.0 * need / (s * peak.hbm_bytes_per_s)


def read(ctx):
    import jax

    return roofline(ctx, PEAKS.get(jax.devices()[0].device_kind))
