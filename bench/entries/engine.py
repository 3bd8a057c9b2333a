"""Entry ``engine``: a cold solve through ``repro.SolverSession`` on the
paper's K-way engine (``engine:bsr`` or ``engine:chunk``).

As entry ``session`` (whose ``Session`` this one extends), the traffic file
names the backend and its options; options it leaves out take the program's
defaults.  One grain is one step of ``SolverSession.run()``: one engine
chunk, ``chunk_rounds`` exchange cycles of up to ``max_inner`` local rounds.

The engine's loop state is more than one tuple: the sharded
``EngineState``, the executor's bucket-to-row map and the operands it has
permuted with the buckets, the rebalancer's state, and the driver's host
fields (the last |F|_1, the chunk count that ``max_chunks`` caps, the
push-count baseline of the control plane, the move log).  ``mark`` keeps
all of them and ``rewind`` puts them back, each a swap of references (a
copy of the few host fields), so the window replays its span exactly.
``fluid()`` is the session entry's: the driver's F holds the fluid still
in the outboxes.

Set-up first checks that the layout the program's bucket rule gives this
graph fits the chip: the tile pool [R, T, S, S] and the per-edge arrays
[R, E].  A program whose layout does not fit stops there, with a message,
instead of building tens of GB on the host.
"""
from __future__ import annotations

import copy
import importlib.util
from pathlib import Path

import numpy as np

_SESSION = Path(__file__).with_name("session.py")
_spec = importlib.util.spec_from_file_location("bench_entry_engine_base",
                                               _SESSION)
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

ITEMSIZE = 4  # float32 tiles and weights, int32 indices
EDGE_ARRAYS = 4  # src_slot, dst_bucket, dst_slot and wgt, per edge slot


def layout_bytes(indptr: np.ndarray, indices: np.ndarray, n: int,
                 options: dict) -> dict:
    """The engine layout the program's bucket rule builds for this graph,
    counted from the raw links without building it: rows R, bucket size S,
    tiles a row T (distinct destination buckets of the fullest bucket),
    edge slots a row E, and the bytes of the tile pool and the per-edge
    arrays on the device."""
    from repro.api.options import SolverOptions
    from repro.api.session import _bsr_buckets_per_dev

    opts = SolverOptions(**options).validated()
    k = opts.k or 1
    bpd = _bsr_buckets_per_dev(n, k, opts)
    real = k * (bpd - opts.headroom)
    s = -(-n // real)
    deg = np.diff(np.asarray(indptr, dtype=np.int64))
    src_b = np.repeat(np.arange(n, dtype=np.int64) // s, deg)
    dst_b = np.asarray(indices, dtype=np.int64) // s
    own = np.bincount(src_b[src_b == dst_b], minlength=real) > 0
    away = src_b != dst_b
    pairs = np.unique(src_b[away] * real + dst_b[away])
    t = int((np.bincount(pairs // real, minlength=real) + own).max())
    e = int(np.bincount(np.arange(n) // s, weights=deg,
                        minlength=real).max())
    r = k * bpd
    return {"rows": r, "bucket": s, "tiles_per_row": t, "edge_cap": e,
            "tile_bytes": r * t * s * s * ITEMSIZE,
            "edge_bytes": r * e * EDGE_ARRAYS * ITEMSIZE}


def device_limit():
    """The bytes the first device may hold, or None where it does not
    say (the CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


class Session(base.Session):
    def mark(self):
        d = self.s._driver
        ex = d.ex
        return (ex.state, ex.row_of_bucket.copy(), ex.chunk_operands(),
                copy.deepcopy(d.engine.rebalancer), d._resid, d._chunks,
                d._prev_ops.copy(), len(d._moves))

    def rewind(self, mark) -> None:
        d = self.s._driver
        ex = d.ex
        (ex.state, rob, operands, rebalancer, d._resid, d._chunks, prev_ops,
         n_moves) = mark
        ex.row_of_bucket = rob.copy()
        (ex.w, ex.src_slot, ex.dst_bucket, ex.dst_slot, ex.wgt) = operands[:5]
        if ex.tiles is not None:
            ex.tiles, ex.tile_dst, ex.slot_out_deg = operands[5:8]
        d.engine.rebalancer = copy.deepcopy(rebalancer)
        d._prev_ops = prev_ops.copy()
        del d._moves[n_moves:]
        self._it = self.s.run()


class Entry(base.Entry):
    def __init__(self, instance, traffic: dict, limit=None):
        limit = device_limit() if limit is None else limit
        if limit is not None:
            lay = layout_bytes(instance.indptr, instance.indices, instance.n,
                               traffic.get("options", {}))
            need = lay["tile_bytes"] + lay["edge_bytes"]
            if need > limit:
                raise RuntimeError(
                    f"{traffic['method']}: the program's layout of this "
                    f"graph does not fit the device: {need} bytes of tiles "
                    f"and edge arrays against {limit} ({lay})")
        super().__init__(instance, traffic)

    def open(self) -> Session:
        """A new session, its state and operands on the device before it
        returns."""
        import jax

        import repro

        s = repro.SolverSession(self.problem, method=self.method,
                                **self.options)
        ex = s._driver.ex
        jax.block_until_ready((ex.state, ex.chunk_operands()))
        return Session(s, self.tol)


def prepare(instance, traffic: dict) -> Entry:
    return Entry(instance, traffic)
