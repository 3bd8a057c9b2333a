"""Dry-run lowering machinery on a small fake-device mesh (subprocess so the
main test process keeps seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, {src!r})
    import jax

    from repro.core.distributed import EngineConfig
    from repro.launch.dryrun import lower_engine_chunk, parse_collectives
    from repro.parallel.compat import cost_analysis_dict

    # the engine chunk at k = 4 through the path lower_solver takes, at a
    # small size: 4 rows of 16 slots a device, 3 exchanges a chunk
    k, b_loc, s, chunk_rounds = 4, 4, 16, 3
    cfg = EngineConfig(k=k, target_error=1e-6, eps=0.15,
                       buckets_per_dev=b_loc, headroom=1,
                       chunk_rounds=chunk_rounds)
    compiled = lower_engine_chunk(jax.devices()[:k], cfg, bucket_size=s,
                                  edge_cap=4 * s)
    assert cost_analysis_dict(compiled).get("flops", 0) > 0
    assert compiled.memory_analysis().argument_size_in_bytes > 0
    colls = parse_collectives(compiled.as_text())
    # the exchange's psum_scatter: a reduce-scatter of one device's
    # [b_loc * s] share, or the full-outbox all-reduce a backend may
    # lower it to (the loop's pmax and the residual sums are scalars)
    share = b_loc * s * 4
    exchange = [c for c in colls
                if c["op"] in ("reduce-scatter", "all-reduce")
                and c["dtype"] == "f32" and c["result_bytes"] >= share]
    assert exchange, sorted((c["op"], c["dtype"], c["result_bytes"])
                            for c in colls)
    assert all(c["group_size"] == k for c in exchange), exchange
    n = sum(c["trips"] for c in exchange)
    assert n == chunk_rounds, (n, chunk_rounds, exchange)
    print("DRYRUN_MACHINERY_OK", len(colls), n)
    """
)


def test_engine_chunk_collectives():
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=src)],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN_MACHINERY_OK" in r.stdout
