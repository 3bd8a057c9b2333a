"""Multi-pod dry-run: lower + compile the paper's engine chunk.

Proves on 256 / 512 placeholder devices that the engine's ``shard_map``
chunk is coherent on a production mesh: ``jax.jit(...).lower(...)
.compile()`` must succeed, and the compiled HLO yields the memory, cost
and per-device collective inventory (``parse_collectives``) recorded in
the output JSON.

Usage:
  python -m repro.launch.dryrun --solver [--mesh single|multi|both]

Outputs: results/dryrun/solver__chunk__<mesh>.json
"""
import argparse
import json
import os
import re
import time

import jax
import numpy as np

from repro.parallel.compat import cost_analysis_dict, mesh_axis_types_kw

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^\s]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([^\]]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{([^}]*)\}")


_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"while\(.*?\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"to_apply=%?([\w.\-]+)")


def _split_computations(hlo_text: str):
    """{computation name: [lines]} from post-optimization HLO text.

    Headers are column-0 lines ending in ``{`` whose first token is the
    computation name (possibly prefixed with ENTRY); parameter lists can
    contain nested tuple parens, so only the name is parsed.
    """
    comps = {}
    cur = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if (not line.startswith(" ") and stripped.endswith("{")
                and (stripped.startswith("%")
                     or stripped.startswith("ENTRY"))):
            m = _COMP_HDR_RE.match(stripped)
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if cur is not None:
            if stripped == "}":
                cur = None
            else:
                comps[cur].append(stripped)
    return comps


_SHAPE_RE = re.compile(r"\w+\[(\d+)[,\]]")


def _while_trips(line: str, trip_candidates) -> int:
    """Trip count of a while from its carried-tuple shapes.

    A lax.scan of length T stacks its xs/ys with leading dim T; the while
    op's printed result tuple exposes those leading dims.  We vote among
    the candidate trip counts for this loop's NESTING DEPTH (arrays
    stacked for an inner scan thread through outer loops too, so
    depth-blind voting mis-attributes an outer loop to the inner count).
    """
    if not trip_candidates:
        return 1
    votes = {}
    for m in _SHAPE_RE.finditer(line.split(" while(")[0]):
        d = int(m.group(1))
        if d in trip_candidates:
            votes[d] = votes.get(d, 0) + 1
    if not votes:
        return 1
    return max(votes.items(), key=lambda kv: kv[1])[0]


def _loop_multipliers(comps, trip_candidates=()):
    """Multiplier per computation = product of enclosing while trip counts.

    XLA:CPU's cost_analysis counts while bodies ONCE, so the collective
    inventory must re-apply the trip counts.  ``trip_candidates`` is
    either a flat set (depth-blind) or a list of per-depth sets:
    ``[ {outermost trips}, {depth-1 trips}, ... ]`` — a while at nesting
    depth d only votes within candidates[d] (falling back to the last
    entry for deeper loops).
    """
    if trip_candidates and isinstance(trip_candidates, (set, frozenset)):
        by_depth = [set(trip_candidates)]
    else:
        by_depth = [set(s) for s in trip_candidates] or [set()]

    children = {}  # comp -> [(child_comp, kind, payload)]
    for name, lines in comps.items():
        for line in lines:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                children.setdefault(name, []).append(("while", body, line))
                children.setdefault(name, []).append(("call", cond, None))
            elif "to_apply=" in line and "fusion" not in line:
                cm = _CALL_RE.search(line)
                if cm:
                    children.setdefault(name, []).append(
                        ("call", cm.group(1), None))

    referenced = {c for kids in children.values() for _, c, _ in kids}
    roots = [n for n in comps if n not in referenced]
    mult = {}

    def walk(name, m, depth):
        if m <= mult.get(name, 0):
            return
        mult[name] = m
        for kind, child, line in children.get(name, []):
            if kind == "while":
                cand = by_depth[min(depth, len(by_depth) - 1)]
                trips = _while_trips(line, cand)
                walk(child, m * max(trips, 1), depth + 1)
            else:
                walk(child, m, depth)

    for r in roots:
        walk(r, 1, 0)
    return mult


def parse_collectives(hlo_text: str, trip_candidates=()):
    """Loop-aware per-device collective inventory from post-SPMD HLO.

    Each collective record carries ``trips`` — the product of enclosing
    while-loop trip counts — and ``moved_bytes`` already scaled by it.
    ``trip_candidates`` are the known scan lengths of the lowered program.

    Bytes-moved estimate per op (ring algorithms, per participating device):
      all-reduce:        2·b·(g-1)/g      (b = result bytes)
      all-gather:        b·(g-1)/g        (b = full gathered result)
      reduce-scatter:    b·(g-1)          (b = scattered result)
      all-to-all:        b·(g-1)/g
      collective-permute: b
    """
    comps = _split_computations(hlo_text)
    mult = _loop_multipliers(comps, trip_candidates)
    out = []
    for comp_name, lines in comps.items():
        trips = mult.get(comp_name, 1)
        for line in lines:
            m = _COLL_RE.search(line)
            if not m:
                continue
            dtype, dims, op = m.group(1), m.group(2), m.group(3)
            if dtype not in _DTYPE_BYTES:
                continue
            n_elem = 1
            if dims:
                for d in dims.split(","):
                    n_elem *= int(d)
            nbytes = n_elem * _DTYPE_BYTES[dtype]
            g = 1
            iota = ""
            gm = _GROUPS_RE.search(line)
            if gm:
                g = int(gm.group(2))
                iota = gm.group(3)
            else:
                gl = _GROUPS_LIST_RE.search(line)
                if gl:
                    first = gl.group(1).split("}")[0].strip("{} ")
                    g = len([t for t in first.split(",")
                             if t.strip() != ""])
            if op == "all-reduce":
                moved = 2.0 * nbytes * (g - 1) / max(g, 1)
            elif op == "all-gather":
                moved = nbytes * (g - 1) / max(g, 1)
            elif op == "reduce-scatter":
                moved = float(nbytes) * (g - 1)
            elif op == "all-to-all":
                moved = nbytes * (g - 1) / max(g, 1)
            else:  # collective-permute
                moved = float(nbytes)
            out.append(
                {"op": op, "dtype": dtype, "result_bytes": nbytes,
                 "group_size": g, "groups_iota": iota, "trips": trips,
                 "moved_bytes": moved * trips}
            )
    return out


def classify_link(rec, n_single_pod=256):
    """DCN if the group stride spans pods (iota factor >= chips/pod)."""
    iota = rec.get("groups_iota", "")
    if rec["group_size"] == 2 and iota.startswith("2,"):
        return "dcn"  # leading pod-axis split
    # groups over contiguous in-pod ranges are ICI
    return "ici"


def _by_op(colls):
    agg = {}
    for c in colls:
        a = agg.setdefault(c["op"], {"count": 0, "moved_bytes": 0.0})
        a["count"] += 1
        a["moved_bytes"] += c["moved_bytes"]
    return agg


def _gb(x):
    return "n/a" if x is None else f"{x/2**30:.2f}GiB"


def lower_engine_chunk(devices, cfg, bucket_size: int, edge_cap: int):
    """Lower + compile the engine's jitted chunk from abstract arguments.

    ``devices`` are flattened into the engine's one ``pid`` axis (so
    ``cfg.k == len(devices)``); the chunk is the per-edge
    (``segment_sum``) path with ``cfg.buckets_per_dev`` rows of
    ``bucket_size`` slots and ``edge_cap`` edges per device row.  No host
    array is built.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import DistributedEngine, EngineState

    k = cfg.k
    flat_mesh = jax.sharding.Mesh(
        np.asarray(devices).reshape(-1), ("pid",), **mesh_axis_types_kw(1)
    )
    assert flat_mesh.devices.size == k, (flat_mesh.devices.size, k)
    r = k * cfg.buckets_per_dev
    eng = DistributedEngine.__new__(DistributedEngine)
    eng.a = type("A", (), {"bucket_size": bucket_size, "n_rows": r,
                           "edge_cap": edge_cap})()
    eng.cfg = cfg
    eng.axis = "pid"
    eng.mesh = flat_mesh
    run_chunk = DistributedEngine._build_chunk(eng)

    sds = lambda s, d: jax.ShapeDtypeStruct(s, d)
    dt = jnp.float32
    row = lambda *s: sds(tuple(s), dt)
    rowi = lambda *s: sds(tuple(s), jnp.int32)
    state = EngineState(
        f=row(r, bucket_size),
        h=row(r, bucket_size),
        outbox=row(k, r * bucket_size),
        t=row(k),
        pos_of_bucket=rowi(r),
        ops=sds((k, 2), jnp.uint32),
        rounds=sds((), jnp.int32),
        armed=sds((k, 2), jnp.uint32),
    )
    sh = lambda spec: NamedSharding(flat_mesh, spec)
    state_sh = EngineState(
        f=sh(P("pid")), h=sh(P("pid")), outbox=sh(P("pid")),
        t=sh(P("pid")), pos_of_bucket=sh(P()), ops=sh(P("pid")),
        rounds=sh(P()), armed=sh(P("pid")),
    )
    args = (state, row(r, bucket_size), rowi(r, edge_cap),
            rowi(r, edge_cap), rowi(r, edge_cap), row(r, edge_cap))
    shards = (state_sh, sh(P("pid")), sh(P("pid")), sh(P("pid")),
              sh(P("pid")), sh(P("pid")))
    with flat_mesh:
        lowered = jax.jit(
            lambda s, w, ss, db, dsl, wg: run_chunk(s, w, ss, db, dsl, wg),
            in_shardings=shards,
        ).lower(*args)
        return lowered.compile()


def lower_solver(multi_pod: bool, out_dir: str = None, verbose=True):
    """Dry-run the paper's production engine chunk on the big mesh.

    Solver sizing: web-scale synthetic instance, N = 16.7M nodes packed in
    4096-slot buckets, 6 real + 2 headroom buckets per device.
    """
    from repro.core.distributed import EngineConfig
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    k = int(np.prod(mesh.devices.shape))
    cfg = EngineConfig(
        k=k, target_error=1e-8, eps=0.15,
        buckets_per_dev=8, headroom=2, chunk_rounds=1,
    )
    bucket_size = 4096
    edge_cap = bucket_size * 16  # L/N ~ 12.9 with skew headroom
    r = k * cfg.buckets_per_dev
    t0 = time.time()
    compiled = lower_engine_chunk(mesh.devices, cfg, bucket_size, edge_cap)
    mem = compiled.memory_analysis()
    ca = cost_analysis_dict(compiled)
    colls = parse_collectives(compiled.as_text())
    for c in colls:
        c["link"] = classify_link(c)
    result = {
        "arch": "diteration-solver",
        "cell": f"N{r*bucket_size}_L{r*edge_cap}",
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "n_devices": k,
        "compile_s": round(time.time() - t0, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "cost": {"flops_per_device": ca.get("flops"),
                 "bytes_per_device": ca.get("bytes accessed")},
        "collectives": {
            "count": len(colls),
            "moved_bytes_total": sum(c["moved_bytes"] for c in colls),
            "by_op": _by_op(colls),
            "records": colls[:100],
        },
        "kind": "solve",
        "meta": {"n": r * bucket_size, "edges": r * edge_cap},
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"solver__chunk__{result['mesh']}.json"), "w") as f:
            json.dump(result, f, indent=1)
    if verbose:
        print(f"[OK] solver × {result['mesh']}: "
              f"flops/dev={result['cost']['flops_per_device']:.3e} "
              f"colls={result['collectives']['count']}")
    return result


def main():
    ap = argparse.ArgumentParser(
        description="Lower + compile the engine chunk on a production mesh "
        "of placeholder CPU devices.")
    ap.add_argument("--solver", action="store_true",
                    help="lower the paper's engine chunk (the one entry)")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if not args.solver:
        ap.error("nothing to lower: pass --solver")
    # the placeholder devices must exist before jax initialises its backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    out = args.out or os.path.abspath(RESULTS_DIR)
    for mp in {"single": [False], "multi": [True],
               "both": [False, True]}[args.mesh]:
        lower_solver(mp, out_dir=out)


if __name__ == "__main__":
    main()
