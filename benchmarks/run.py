"""Benchmark harness: one function per paper-level claim/table.

The source paper (LATTE'21, 2 pages) has no numbered tables; its claims
map to these harnesses:

  table1_specialization  — the flow itself: per-pass cost + what each
                           pass buys (modeled step time), per workload
                           (the paper's flexibility/specialization
                           trade-off).
  table2_kernels         — kernel microbenchmarks vs the jnp oracle
                           (CPU wall time) + plan-derived VMEM/roofline
                           columns for the TPU target.
  table3_end_to_end      — reduced-config train step wall time.
  table4_roofline        — the dry-run roofline table (reads
                           results/dryrun/*.json; see EXPERIMENTS.md).

Prints ``name,us_per_call,derived`` CSV.

Run:  PYTHONPATH=src python -m benchmarks.run [--only tableN]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _time(fn, *args, n=10, warmup=2) -> float:
    """Median wall time per call, in us."""
    us, _ = _time_keep(fn, *args, n=n, warmup=warmup)
    return us


def _time_keep(fn, *args, n=10, warmup=2):
    """Median wall time per call in us, plus the last call's result
    (so callers time a computation AND use it without re-running)."""
    out = None
    for _ in range(warmup):
        out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6), out


#: rows collected for the optional --json machine-readable dump
ROWS: list = []


def emit(name: str, us: float, derived: str = "") -> None:
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------
def table1_specialization() -> None:
    from repro.configs import ShapeConfig, get_arch, get_shape
    from repro.core.costmodel import MeshModel, estimate_step
    from repro.core.describe import describe_program
    from repro.core.passes import (CommunicationPass, DataOrganizationPass,
                                   LayoutPass, LocalPartitioningPass)
    from repro.core.pipeline import specialize
    from repro.hw import get_target

    # finetune_128: small-batch TP training is collective-bound -> the
    # communication pass switches the DP grad reduction to int8+EF and
    # the collective_ms vs collective_raw_ms columns show the cut
    cases = [("qwen3-8b", "train_4k", None),
             ("qwen3-8b", "finetune_128",
              (ShapeConfig("finetune_128", "train", 128, 8), (8, 2))),
             ("llama4-maverick-400b-a17b", "train_4k", None),
             ("qwen2-vl-72b", "decode_32k", None),
             ("mamba2-2.7b", "long_500k", None)]
    stages = [
        ("data_org", [DataOrganizationPass]),
        ("+layout", [DataOrganizationPass, LayoutPass]),
        ("+comm", [DataOrganizationPass, LayoutPass, CommunicationPass]),
        ("full", [DataOrganizationPass, LayoutPass, CommunicationPass,
                  LocalPartitioningPass]),
    ]
    default_mesh = MeshModel(axes=("data", "model"), shape=(16, 16))
    tgt = get_target()
    for arch, shape_name, custom in cases:
        shape_cfg = get_shape(shape_name) if custom is None else custom[0]
        mesh_shape = (16, 16) if custom is None else custom[1]
        mesh = MeshModel(axes=("data", "model"), shape=mesh_shape) \
            if custom is not None else default_mesh
        ir = describe_program(get_arch(arch), shape_cfg)
        for label, passes in stages:
            # time the flow itself (cache=False) and KEEP the timed
            # result instead of running specialize() a second time
            us, plan = _time_keep(
                lambda: specialize(arch, shape_cfg, passes=passes,
                                   mesh_shape=mesh_shape, cache=False),
                n=5, warmup=1)
            training = shape_cfg.kind == "train"
            schedule = (plan.comm.grad_schedule
                        if plan.comm.grad_schedule != "none"
                        else "reduce_scatter")
            est = estimate_step(
                ir, plan.axis_rules, mesh, tgt, training=training,
                grad_schedule=schedule,
                grad_bits=8 if plan.comm.compresses_gradients else None)
            est_raw = estimate_step(
                ir, plan.axis_rules, mesh, tgt, training=training,
                grad_schedule=schedule)
            grad_comm = "none" if not training else (
                f"{schedule}+int8_ef" if plan.comm.compresses_gradients
                else schedule)
            emit(f"specialize/{arch}@{shape_name}/{label}", us,
                 f"modeled_step_ms={est.step_time_overlap*1e3:.1f};"
                 f"bound={est.bound};grad_comm={grad_comm};"
                 f"collective_ms={est.collective_s*1e3:.2f};"
                 f"collective_raw_ms={est_raw.collective_s*1e3:.2f}")
    # the plan store in action: cold compile vs zero-copy in-memory hit
    # vs content-addressed disk hit (fresh-process restart path)
    import tempfile
    from repro.core import planstore
    arch, shape_name, _ = cases[0]
    plan_dir = tempfile.mkdtemp(prefix="repro_plan_bench_")
    store = planstore.get_store(plan_dir)
    us_cold = _time(lambda: specialize(arch, shape_name, cache=False),
                    n=5, warmup=1)
    emit(f"plan_cache/{arch}@{shape_name}/cold_compile", us_cold,
         "full pipeline run, no cache")
    _, plan = _time_keep(
        lambda: specialize(arch, shape_name, plan_dir=plan_dir),
        n=1, warmup=0)                            # warm the two tiers
    us_mem = _time(lambda: specialize(arch, shape_name, plan_dir=plan_dir),
                   n=20, warmup=1)
    emit(f"plan_cache/{arch}@{shape_name}/mem_hit", us_mem,
         f"zero-copy frozen view;speedup_vs_cold={us_cold/us_mem:.0f}x;"
         f"hash={plan.content_hash()[:12]}")

    def _disk_hit():
        store.clear()                             # drop the memory tier only
        return specialize(arch, shape_name, plan_dir=plan_dir)
    us_disk = _time(_disk_hit, n=10, warmup=1)
    emit(f"plan_cache/{arch}@{shape_name}/disk_hit", us_disk,
         f"content-addressed reload+hash-verify;"
         f"vs_warm_process_cold={us_cold/us_disk:.1f}x "
         f"(tier value = surviving restarts, not beating warm recompiles)")

    _wire_compression_rows()


def _wire_compression_rows() -> None:
    """finetune_128's modeled wire cut, measured: the lowered int8+EF
    train step vs the fp32 baseline on a real 2x4 host mesh (subprocess
    with forced devices), with the wire proof counted off the compiled
    HLO — gradient-sized all-reduces whose replica groups span the DATA
    axis, by dtype (model-axis megatron activation reduces are shipped
    identically by both steps and excluded by the replica-group test),
    and the loss gap after 4 steps showing EF keeps the trajectory."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import re, time
        import numpy as np
        import jax
        from repro.configs import ShapeConfig, get_arch
        from repro.core.pipeline import specialize
        from repro.launch.mesh import make_mesh
        from repro.models import synthetic_batch
        from repro.optim.adamw import OptConfig
        from repro.train.trainer import Trainer, TrainerConfig

        arch = get_arch("qwen3-8b").reduced()
        shape = ShapeConfig("finetune_wire", "train", 64, 8)
        mesh = make_mesh((2, 4), ("data", "model"))

        def run(gc):
            plan = specialize(arch, shape, mesh_axes=("data", "model"),
                              mesh_shape=(2, 4), cache=False,
                              grad_compression=gc)
            tr = Trainer(plan, mesh, TrainerConfig(n_steps=1, ckpt_every=0),
                         opt_cfg=OptConfig(total_steps=8),
                         arch=arch, shape=shape)
            state = tr.init_state()
            losses = []
            for i in range(4):
                b = synthetic_batch(arch, shape, jax.random.PRNGKey(50 + i))
                state, m = tr.step_fn(state, b)
                losses.append(float(m["loss"]))
            # time the canonical jitted step (state threads through the
            # donation) — a re-jit of the bare fn would drop the batch's
            # data-axis sharding and with it the very wire being counted
            b = synthetic_batch(arch, shape, jax.random.PRNGKey(50))
            txt = tr.step_fn.lower(state, b).compile().as_text()
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                state, m = tr.step_fn(state, b)
                jax.block_until_ready((state, m))
                ts.append(time.perf_counter() - t0)
            # The wire = collectives whose replica groups span the DATA
            # axis of the (2,4) data x model mesh: {{0,4},{1,5},...} in
            # literal form, [4,2]<=[2,4] in iota form. Model-axis
            # activation reduces ({{0,1,2,3},...} / [2,4]<=[8]) are the
            # same in both steps; size alone cannot separate the two on
            # the reduced arch (both top out at 16384 elements).
            fx = sx = 0
            for line in txt.splitlines():
                m = re.search(
                    r"= (\\w+)\\[([\\d,]*)\\]\\S* (all-reduce|"
                    r"reduce-scatter)\\(", line)
                if m is None:
                    continue
                n = int(np.prod([int(t) for t in m.group(2).split(",")
                                 if t] or [1]))
                xdata = ("replica_groups={{0,4}" in line
                         or "replica_groups=[4,2]<=[2,4]" in line)
                if n < 4096 or not xdata:
                    continue   # scales, loss/grad-norm scalars, TP reduces
                if m.group(1) in ("f32", "bf16", "f64"):
                    fx += 1
                elif m.group(1) == "s16":
                    sx += 1
            return float(np.median(ts)) * 1e6, losses, fx, sx

        us_off, l_off, fx_off, _ = run("off")
        us_on, l_on, fx_on, sx_on = run("on")
        gap = max(abs(a - b) for a, b in zip(l_on, l_off))
        print("ROW=train_step/finetune_128/fp32_wire,%.1f,"
              "grad_reduce=fp32;grad_sized_xdata_float_allreduce=%d"
              % (us_off, fx_off))
        print("ROW=train_step/finetune_128/int8_ef_wire,%.1f,"
              "grad_reduce=int16 code sum;grad_sized_xdata_s16_allreduce=%d;"
              "grad_sized_xdata_float_allreduce=%d;loss_gap_4steps=%.1e;"
              "vs_fp32=%.2fx" % (us_on, sx_on, fx_on, gap,
                                 us_off / max(us_on, 1e-9)))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": str(
            Path(__file__).resolve().parents[1] / "src"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    rows = [l[4:] for l in out.stdout.splitlines() if l.startswith("ROW=")]
    if out.returncode == 0 and rows:
        for row in rows:
            name, us, derived = row.split(",", 2)
            emit(name, float(us), derived)
    else:
        emit("train_step/finetune_128/int8_ef_wire", 0.0,
             "subprocess failed: " + out.stderr.strip()[-200:])


# ---------------------------------------------------------------------
def table2_kernels() -> None:
    from repro.core.pipeline import specialize
    from repro.hw import get_target
    from repro.kernels import ref

    tgt = get_target()
    plan = specialize("qwen3-8b", "train_4k")
    key = jax.random.PRNGKey(0)
    B, S, H, K, D = 1, 1024, 8, 4, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, D)).astype(jnp.bfloat16)
    fa = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    bp = plan.partitions["flash_attention"]
    flops = 4 * B * S * S * H * D * 0.5
    emit("kernel/flash_attention/ref_cpu", _time(fa, q, k, v),
         f"blocks={bp.blocks};vmem_2bank_MiB={2*bp.vmem_bytes/2**20:.1f};"
         f"tpu_roofline_us={flops/tgt.peak_bf16_flops*1e6:.1f}")

    qd = jax.random.normal(ks[0], (8, H, D)).astype(jnp.bfloat16)
    kd = jax.random.normal(ks[1], (8, 4096, K, D)).astype(jnp.bfloat16)
    vd = jax.random.normal(ks[2], (8, 4096, K, D)).astype(jnp.bfloat16)
    da = jax.jit(lambda q, k, v: ref.decode_attention_ref(
        q, k, v, cache_len=jnp.int32(4096)))
    cache_bytes = kd.nbytes + vd.nbytes
    emit("kernel/decode_attention/ref_cpu", _time(da, qd, kd, vd),
         f"cache_MiB={cache_bytes/2**20:.0f};"
         f"tpu_stream_us={cache_bytes/tgt.hbm_bw*1e6:.1f}")

    _decode_step_rows(ks, H, K, D)
    _combine_topology_rows(H, K, D)
    _paged_occupancy_rows(ks, H, K, D)
    _admission_occupancy_rows(ks, H, K, D)
    _paged_2d_occupancy_rows(H, K, D)
    _prefix_overlap_rows()
    _tiered_park_rows()
    _disagg_interference_rows()

    plan2 = specialize("mamba2-2.7b", "train_4k")
    bp2 = plan2.partitions["ssd_scan"]
    x = jax.random.normal(ks[0], (1, 512, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 512, 8)))
    A = -jnp.exp(jax.random.normal(ks[2], (8,)) * 0.3)
    Bm = jax.random.normal(ks[1], (1, 512, 8, 64))
    Cm = jax.random.normal(ks[2], (1, 512, 8, 64))
    sc = jax.jit(lambda *a: ref.ssd_scan_ref(*a)[0])
    emit("kernel/ssd_scan/ref_cpu", _time(sc, x, dt, A, Bm, Cm, n=5),
         f"blocks={bp2.blocks}")

    a = jax.random.normal(ks[0], (1024, 1024)).astype(jnp.bfloat16)
    b = jax.random.normal(ks[1], (1024, 1024)).astype(jnp.bfloat16)
    mm = jax.jit(ref.tiled_matmul_ref)
    bp3 = plan.partitions["tiled_matmul"]
    emit("kernel/tiled_matmul/ref_cpu", _time(mm, a, b),
         f"blocks={bp3.blocks};"
         f"tpu_roofline_us={2*1024**3/tgt.peak_bf16_flops*1e6:.2f}")


def _decode_step_rows(ks, H, K, D) -> None:
    """Decode-step microbench at *mixed batch fill* (staggered per-slot
    positions, the continuous-batching steady state): xla append+mask vs
    the flash-decode combine vs the real shard_map seq-sharded path."""
    import os
    import subprocess
    import sys
    import textwrap

    from repro.dist.flash_decode import flash_decode
    from repro.launch.mesh import make_mesh
    from repro.models import lm

    B, S = 8, 4096
    q1 = jax.random.normal(ks[0], (B, 1, H, D)).astype(jnp.bfloat16)
    kn = jax.random.normal(ks[1], (B, 1, K, D)).astype(jnp.bfloat16)
    vn = jax.random.normal(ks[2], (B, 1, K, D)).astype(jnp.bfloat16)
    kc = jax.random.normal(ks[1], (B, S, K, D)).astype(jnp.bfloat16)
    vc = jax.random.normal(ks[2], (B, S, K, D)).astype(jnp.bfloat16)
    # staggered fill: slots range from nearly-empty to nearly-full
    pos = jnp.asarray(np.linspace(64, S - 1, B).astype(np.int32))
    fill = f"fill={int(pos.min())}..{int(pos.max())}/{S}"

    from repro.models.attention import attention_decode

    def xla_step(q, kn, vn, kc, vc, pos):
        kc = lm.append_kv(kc, kn, pos)
        vc = lm.append_kv(vc, vn, pos)
        return attention_decode(q, kc, vc, cache_len=pos + 1), kc, vc

    emit("decode_step/xla/mixed_fill",
         _time(jax.jit(xla_step), q1, kn, vn, kc, vc, pos), fill)

    mesh1 = make_mesh((len(jax.devices()), 1), ("data", "model"))
    fd = jax.jit(lambda *a: flash_decode(*a, mesh=mesh1))
    emit("decode_step/flash/mixed_fill",
         _time(fd, q1, kn, vn, kc, vc, pos, 0),
         fill + ";single-shard online-softmax combine")

    # the seq-sharded shard_map path needs >1 host device: subprocess
    # with a forced device count (the parent keeps the single real CPU)
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np, time
        from repro.dist.flash_decode import flash_decode
        from repro.launch.mesh import make_mesh
        B, S, H, K, D = {B}, {S}, {H}, {K}, {D}
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D)).astype(jnp.bfloat16)
        kn = jax.random.normal(ks[1], (B, 1, K, D)).astype(jnp.bfloat16)
        vn = jax.random.normal(ks[2], (B, 1, K, D)).astype(jnp.bfloat16)
        kc = jax.random.normal(ks[1], (B, S, K, D)).astype(jnp.bfloat16)
        vc = jax.random.normal(ks[2], (B, S, K, D)).astype(jnp.bfloat16)
        pos = jnp.asarray(np.linspace(64, S - 1, B).astype(np.int32))
        mesh = make_mesh((1, 2), ("data", "model"))
        fn = jax.jit(lambda *a: flash_decode(*a, mesh=mesh))
        for _ in range(2):
            jax.block_until_ready(fn(q, kn, vn, kc, vc, pos, 0))
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, kn, vn, kc, vc, pos, 0))
            ts.append(time.perf_counter() - t0)
        print("US=%.1f" % (float(np.median(ts)) * 1e6))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": str(
            Path(__file__).resolve().parents[1] / "src"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    us_line = [l for l in out.stdout.splitlines() if l.startswith("US=")]
    if out.returncode == 0 and us_line:
        emit("decode_step/shard_map_flash/mixed_fill",
             float(us_line[0][3:]),
             fill + ";seq-sharded over model=2 (owning-shard append + "
             "3-term combine)")
    else:
        emit("decode_step/shard_map_flash/mixed_fill", 0.0,
             "subprocess failed: " + out.stderr.strip()[-200:])


def _combine_topology_rows(H, K, D) -> None:
    """The model-axis softmax-combine topologies head-to-head: flat
    (pmax + 2 psums), ring (neighbor ppermute walk), and bidirectional
    ring at model degrees 4 / 8 / 16 on forced host devices — one
    subprocess per degree (the device count is a process-level flag).
    Host-CPU timings rank XLA's fused collectives, not ICI hop counts,
    so the hops=... column carries the modeled cost the thresholds in
    ``choose_combine_topology`` actually compare."""
    import os
    import subprocess
    import sys
    import textwrap

    B, S = 8, 4096
    for m in (4, 8, 16):
        code = textwrap.dedent(f"""
            import jax, jax.numpy as jnp, numpy as np, time
            from repro.core.costmodel import combine_hops
            from repro.launch.mesh import make_mesh
            from repro.dist.flash_decode import flash_decode
            B, S, H, K, D, m = {B}, {S}, {H}, {K}, {D}, {m}
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            q = jax.random.normal(ks[0], (B, 1, H, D)).astype(jnp.bfloat16)
            kn = jax.random.normal(ks[1], (B, 1, K, D)).astype(jnp.bfloat16)
            vn = jax.random.normal(ks[2], (B, 1, K, D)).astype(jnp.bfloat16)
            kc = jax.random.normal(ks[1], (B, S, K, D)).astype(jnp.bfloat16)
            vc = jax.random.normal(ks[2], (B, S, K, D)).astype(jnp.bfloat16)
            pos = jnp.asarray(np.linspace(64, S - 1, B).astype(np.int32))
            mesh = make_mesh((1, m), ("data", "model"))
            for topo in ("flat", "ring", "bidir"):
                fn = jax.jit(lambda *a, t=topo: flash_decode(
                    *a, mesh=mesh, combine=t))
                for _ in range(2):
                    jax.block_until_ready(fn(q, kn, vn, kc, vc, pos, 0))
                ts = []
                for _ in range(10):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(q, kn, vn, kc, vc, pos, 0))
                    ts.append(time.perf_counter() - t0)
                print("ROW=decode_step/combine/%s@tp%d,%.1f,"
                      "hops=%d;seq-sharded model=%d"
                      % (topo, m, float(np.median(ts)) * 1e6,
                         combine_hops(m, topo), m))
        """)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600,
            env={**os.environ, "PYTHONPATH": str(
                Path(__file__).resolve().parents[1] / "src"),
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={m}"})
        rows = [l[4:] for l in out.stdout.splitlines()
                if l.startswith("ROW=")]
        if out.returncode == 0 and rows:
            for row in rows:
                name, us, derived = row.split(",", 2)
                emit(name, float(us), derived)
        else:
            emit(f"decode_step/combine/flat@tp{m}", 0.0,
                 "subprocess failed: " + out.stderr.strip()[-200:])


def _paged_occupancy_rows(ks, H, K, D) -> None:
    """Dense vs paged decode_step at 25/50/100% slot occupancy.

    The dense cache pins ``B x S`` rows no matter how many slots are
    live; the block pool pins only the blocks live slots own — the
    memory column is the reclamation story, the latency column the cost
    of the table gather.  Geometry comes from the same cost model the
    pass uses (``kv_block_len``)."""
    from repro.core.costmodel import kv_block_len
    from repro.models import lm
    from repro.models.attention import attention_decode, attention_decode_paged

    B, S = 8, 4096
    bl = kv_block_len(S)
    nb = S // bl
    q1 = jax.random.normal(ks[0], (B, 1, H, D)).astype(jnp.bfloat16)
    kn = jax.random.normal(ks[1], (B, 1, K, D)).astype(jnp.bfloat16)
    vn = jax.random.normal(ks[2], (B, 1, K, D)).astype(jnp.bfloat16)
    kc = jax.random.normal(ks[1], (B, S, K, D)).astype(jnp.bfloat16)
    vc = jax.random.normal(ks[2], (B, S, K, D)).astype(jnp.bfloat16)
    pool_k = kc.reshape(B * nb, bl, K, D)
    pool_v = vc.reshape(B * nb, bl, K, D)
    row_bytes = 2 * K * D * 2                       # k+v, bf16

    def dense_step(q, kn, vn, kc, vc, pos):
        kc = lm.append_kv(kc, kn, pos)
        vc = lm.append_kv(vc, vn, pos)
        return attention_decode(q, kc, vc, cache_len=pos + 1), kc, vc

    def paged_step(q, kn, vn, kp, vp, tbl, pos):
        kp = lm.append_kv_paged(kp, kn, pos, tbl)
        vp = lm.append_kv_paged(vp, vn, pos, tbl)
        ctx = attention_decode_paged(q, kp, vp, tbl, cache_len=pos + 1)
        return ctx, kp, vp

    dense_fn = jax.jit(dense_step)
    paged_fn = jax.jit(paged_step)
    for occ in (25, 50, 100):
        n_live = max(1, B * occ // 100)
        pos_np = np.zeros((B,), np.int32)
        pos_np[:n_live] = np.linspace(64, S - 1, n_live).astype(np.int32)
        pos = jnp.asarray(pos_np)
        tbl_np = np.full((B, nb), -1, np.int32)
        used = 0
        for b in range(n_live):
            need = int(np.ceil((pos_np[b] + 1) / bl))
            tbl_np[b, :need] = np.arange(used, used + need)
            used += need
        tbl = jnp.asarray(tbl_np)
        dense_mib = B * S * row_bytes / 2**20       # pinned regardless
        paged_mib = used * bl * row_bytes / 2**20   # live blocks only
        fill = f"occ={occ}%;live={n_live}/{B}"
        emit(f"decode_step/dense/occ{occ}",
             _time(dense_fn, q1, kn, vn, kc, vc, pos),
             fill + f";pinned_MiB={dense_mib:.0f}")
        emit(f"decode_step/paged/occ{occ}",
             _time(paged_fn, q1, kn, vn, pool_k, pool_v, tbl, pos),
             fill + f";pinned_MiB={paged_mib:.0f};"
             f"block_len={bl};blocks={used}/{B * nb}")


def _admission_occupancy_rows(ks, H, K, D) -> None:
    """Paged decode_step under ``reserve`` vs ``grant`` admission at
    25/50/100% slot occupancy — the grow-on-demand story next to the
    PR-4/5 baselines.

    Same pool, same kernel, same live slots mid-generation (each ~1/4
    through a full-depth budget): ``reserve`` pins every live slot's
    worst-case block budget from admission on, ``grant`` pins only the
    blocks decode has actually crossed into.  The latency column is the
    non-regression claim (admission mode changes the block *table*, not
    the gather), the pinned_MiB column the sustained-occupancy win."""
    from repro.core.costmodel import kv_block_len
    from repro.models import lm
    from repro.models.attention import attention_decode_paged

    B, S = 8, 4096
    bl = kv_block_len(S)
    nb = S // bl
    q1 = jax.random.normal(ks[0], (B, 1, H, D)).astype(jnp.bfloat16)
    kn = jax.random.normal(ks[1], (B, 1, K, D)).astype(jnp.bfloat16)
    vn = jax.random.normal(ks[2], (B, 1, K, D)).astype(jnp.bfloat16)
    pool_k = jax.random.normal(ks[3], (B * nb, bl, K, D)).astype(jnp.bfloat16)
    pool_v = jax.random.normal(ks[4], (B * nb, bl, K, D)).astype(jnp.bfloat16)
    row_bytes = 2 * K * D * 2                       # k+v, bf16

    def paged_step(q, kn, vn, kp, vp, tbl, pos):
        kp = lm.append_kv_paged(kp, kn, pos, tbl)
        vp = lm.append_kv_paged(vp, vn, pos, tbl)
        ctx = attention_decode_paged(q, kp, vp, tbl, cache_len=pos + 1)
        return ctx, kp, vp

    fn = jax.jit(paged_step)
    for occ in (25, 50, 100):
        n_live = max(1, B * occ // 100)
        pos_np = np.zeros((B,), np.int32)
        # each live slot mid-flight: ~1/4 of a full-depth max_new budget
        pos_np[:n_live] = np.linspace(S // 4, S // 2, n_live) \
            .astype(np.int32)
        pos = jnp.asarray(pos_np)
        for mode in ("reserve", "grant"):
            tbl_np = np.full((B, nb), -1, np.int32)
            used = 0
            for b in range(n_live):
                # grant holds the blocks decode crossed into; reserve
                # holds the full worst-case budget from admission on
                need = int(np.ceil((pos_np[b] + 1) / bl)) \
                    if mode == "grant" else nb
                tbl_np[b, :need] = np.arange(used, used + need) % (B * nb)
                used += need
            tbl = jnp.asarray(tbl_np)
            mib = used * bl * row_bytes / 2**20
            emit(f"decode_step/paged_{mode}/occ{occ}",
                 _time(fn, q1, kn, vn, pool_k, pool_v, tbl, pos),
                 f"occ={occ}%;live={n_live}/{B};admission={mode};"
                 f"pinned_MiB={mib:.0f};block_len={bl};"
                 f"blocks={used}/{B * nb}")


def _prefix_overlap_rows() -> None:
    """Cross-request prefix KV reuse at 0/50/90% session overlap.

    Serving-layer rows (a reduced-arch engine, not a raw kernel): 8
    staggered requests opening with the same 48-token system prompt at
    the given overlap fraction, measured against the overlap0 row — at
    0% nothing matches, so that row IS the no-reuse baseline.  Columns:
    prefill calls over the session (trie-matched admissions ride with
    zero), freshly pinned blocks (aliased prefix blocks are refcount
    bumps, not allocations), and the steady-state decode-tick latency
    at full occupancy (the non-regression claim: sharing changes block
    *tables*, not the gather)."""
    import time as timer

    from repro.configs import get_arch
    from repro.models import lm as rlm
    from repro.models.lm import RunCfg
    from repro.serve.engine import ServeEngine

    arch = get_arch("qwen3-8b").reduced()
    cfg = RunCfg(block_q=16, ssd_chunk=16)
    params = rlm.init_params(arch, jax.random.PRNGKey(0))
    B, bl, max_len, new = 8, 16, 64, 6
    rng = np.random.default_rng(0)
    sysp = rng.integers(0, arch.vocab_size, 3 * bl).astype(np.int32)

    def make_engine():
        eng = ServeEngine(arch, params, cfg, max_batch=B, max_len=max_len,
                          kv_residency="paged", kv_block_len=bl)
        fresh = [0]
        orig_alloc, orig_one = eng._alloc.allocate, eng._alloc.allocate_one
        def counting_alloc(need, group=0):
            got = orig_alloc(need, group)
            if got:
                fresh[0] += len(got)
            return got
        def counting_one(group=0):
            b = orig_one(group)
            if b is not None:
                fresh[0] += 1
            return b
        eng._alloc.allocate = counting_alloc
        eng._alloc.allocate_one = counting_one
        return eng, fresh

    for overlap, n_shared in ((0, 0), (50, 4), (90, 7)):
        prompts = [np.concatenate([sysp, [i + 1]]).astype(np.int32)
                   if i < n_shared else
                   rng.integers(0, arch.vocab_size,
                                (3 * bl + 1,)).astype(np.int32)
                   for i in range(B)]

        # pass A — session counters under staggered 1-per-tick arrivals
        eng, fresh = make_engine()
        eng.submit(prompts[0], max_new_tokens=new)
        eng.step()                  # opener registers the prefix blocks
        arrivals = list(prompts[1:])
        ticks = 0
        while (arrivals or eng.pending or eng.active) and ticks < 400:
            if arrivals:
                eng.submit(arrivals.pop(0), max_new_tokens=new)
            eng.step()
            ticks += 1
        calls, pinned = eng.prefill_calls, fresh[0]
        press = eng.pressure_stats()

        # pass B — steady-state decode-tick latency at full occupancy
        eng, _ = make_engine()
        for p in prompts:
            eng.submit(p, max_new_tokens=new)
        while eng.pending:          # admit everything (prefills + rides)
            eng.step()
        ts = []
        while eng.active:
            t0 = timer.perf_counter()
            eng.step()
            ts.append(timer.perf_counter() - t0)
        emit(f"decode_step/paged_prefix/overlap{overlap}",
             float(np.median(ts)) * 1e6,
             f"overlap={overlap}%;prefill_calls={calls};"
             f"fresh_blocks={pinned};"
             f"rides={press['prefix_rides']};"
             f"hit_tokens={press['prefix_hit_tokens']}")


def _tiered_park_rows() -> None:
    """Decode-tick latency under host-tier park/promote churn at
    0/50/90% per-tick park probability (serving-layer rows, like the
    prefix-overlap ones).

    Seeded forced evictions park victims' KV in the host pool and their
    resumes promote it back mid-run; the row's us column is the median
    decode tick with ``kv_prefetch="on"`` (the double-buffered stage:
    host rows start moving one tick before the resume consumes them),
    the ``prefetch_off_us`` column the same churn with the transfer
    taken synchronously inside the resume tick — the stall the
    lookahead exists to hide.  park0 runs zero churn, so it is the
    untiered decode-tick baseline both columns must stay close to."""
    import time as timer

    from repro.configs import get_arch
    from repro.models import lm as rlm
    from repro.models.lm import RunCfg
    from repro.serve.engine import PreemptionPolicy, ServeEngine

    arch = get_arch("qwen3-8b").reduced()
    cfg = RunCfg(block_q=16, ssd_chunk=16)
    params = rlm.init_params(arch, jax.random.PRNGKey(0))
    B, bl, max_len, new = 8, 16, 64, 24
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, arch.vocab_size, (int(n),)).astype(np.int32)
               for n in rng.integers(5, 16, B)]

    for frac in (0, 50, 90):
        stats = {}
        for prefetch in ("on", "off"):
            eng = ServeEngine(arch, params, cfg, max_batch=B,
                              max_len=max_len, kv_residency="paged",
                              kv_block_len=bl, kv_admission="grant",
                              kv_host_blocks=4 * B, kv_prefetch=prefetch,
                              preemption=PreemptionPolicy(
                                  max_preemptions=64,
                                  backoff_base_ticks=2,
                                  backoff_cap_ticks=2))
            for p in prompts:
                eng.submit(p, max_new_tokens=new)
            while eng.pending:
                eng.step()
            parks = 0
            ts = []
            while (eng.active or eng.preempted) and len(ts) < 2000:
                # deterministic churn: frac% of ticks open with a
                # forced eviction of the most-progressed request
                if frac and eng.active and (len(ts) % 10) < frac // 10:
                    victim = max(eng.active.values(),
                                 key=lambda r: len(r.out_tokens))
                    if victim.out_tokens:
                        eng.preempt(victim.rid)
                        parks += 1
                t0 = timer.perf_counter()
                eng.step()
                ts.append(timer.perf_counter() - t0)
            press = eng.pressure_stats()
            stats[prefetch] = (float(np.median(ts)) * 1e6, parks, press)
        us_on, parks, press = stats["on"]
        us_off = stats["off"][0]
        emit(f"decode_step/tiered/park{frac}", us_on,
             f"park={frac}%;parks={parks};spills={press['spills']};"
             f"promotes={press['promotes']};"
             f"prefetch_off_us={us_off:.1f}")


def _disagg_interference_rows() -> None:
    """Decode-tick tail latency while a long-prompt prefill runs:
    inline (the prefill executes inside an engine tick, stalling every
    decoder for the whole prompt) vs disaggregated (workers prefill
    off-process and stream pool-block-shaped chunks; decode ticks stay
    tick-sized).  The us column is the p99 decode tick over the
    interference window; p50 and the worst single tick ride along in
    derived — the inline max *is* the prefill stall the split removes.
    Prefix reuse is off so the second submit of the long prompt cannot
    alias its prefill away and void the comparison.

    Caveat the rows carry explicitly: on CPU both sides share one
    socket, so the worker's prefill steals the decoder's cores and the
    measured disagg p99 can exceed inline's — the split buys nothing
    when "another device" is the same device.  The ``full_scale``
    column is the cost model's interference verdict for the real
    qwen3-8b decode_32k deployment (prefill stall in decode ticks if
    run inline) — the derivation by which the data-organization pass
    flips ``kv_prefill_mode`` to disagg."""
    import time as timer

    from repro.configs import ShapeConfig, get_arch
    from repro.core.pipeline import specialize
    from repro.models import lm as rlm
    from repro.serve.engine import ServeEngine

    arch = get_arch("qwen3-8b").reduced()
    shape = ShapeConfig("bench_disagg", "decode", 128, 4)
    plan = specialize(arch, shape, mesh_axes=("data", "model"),
                      mesh_shape=(1, 1))
    params = rlm.init_params(arch, jax.random.PRNGKey(0),
                             *plan.padded_sizes())
    rng = np.random.default_rng(0)
    deco = [rng.integers(0, arch.vocab_size, (9,)).astype(np.int32)
            for _ in range(3)]
    long_p = rng.integers(0, arch.vocab_size, (97,)).astype(np.int32)

    # the pass's own paper-scale interference verdict (full arch, 32k)
    full = specialize("qwen3-8b", "decode_32k")
    full_scale = (f"full_scale={full.estimates.get('kv_prefill_mode')}"
                  f"@{full.estimates.get('kv_prefill_stall_ticks', 0):.0f}"
                  "stall_ticks")

    for mode in ("inline", "disagg"):
        eng = ServeEngine.from_plan(
            plan, params, arch=arch, seed=0, kv_prefix_reuse="off",
            kv_prefill_mode=mode,
            disagg_workers=2 if mode == "disagg" else 0)
        # warm every shape this run will hit: the decode step, the
        # short prefill bucket, and one full long-prompt prefill
        # (inline's dense shape / every chunked worker shape)
        eng.submit(long_p, max_new_tokens=2)
        for p in deco:
            eng.submit(p, max_new_tokens=4)
        eng.run_until_idle(30000)
        eng.finished.clear()
        # steady decode, then the interfering long prefill lands
        for p in deco:
            eng.submit(p, max_new_tokens=60)
        while eng.pending:
            eng.step()
        eng.submit(long_p, max_new_tokens=2)
        ts = []
        while (eng.pending or eng.active or eng._disagg) \
                and len(ts) < 300:
            t0 = timer.perf_counter()
            eng.step()
            ts.append(timer.perf_counter() - t0)
        us = [t * 1e6 for t in ts]
        note = (f"p50_us={float(np.percentile(us, 50)):.1f};"
                f"max_us={float(np.max(us)):.1f};"
                f"decoders={len(deco)};prefill_plen={len(long_p)};"
                f"{full_scale}")
        if mode == "disagg":
            assert eng.disagg_dispatches >= 1, "prefill never left process"
            note += f";chunks={eng.disagg_chunks}"
        emit(f"decode_step/disagg/{mode}",
             float(np.percentile(us, 99)), note)
        eng.shutdown()


def _paged_2d_occupancy_rows(H, K, D) -> None:
    """The 2-D pool-sharded paged combine at 25/50/100% occupancy on a
    real 2x4 data×model mesh (subprocess with forced host devices, like
    the shard_map dense row): block dim data-major over both axes,
    batch partitioned across data, per-slot sub-pool block tables —
    next to the dense-stripe baseline the table already carries."""
    import os
    import subprocess
    import sys
    import textwrap

    B, S = 8, 4096
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np, time
        from repro.core.costmodel import kv_block_len
        from repro.dist.flash_decode import (flash_decode_paged,
                                             pool_sharding_kind)
        from repro.launch.mesh import make_mesh
        B, S, H, K, D = {B}, {S}, {H}, {K}, {D}
        dsize, msize = 2, 4
        bl = kv_block_len(S)
        nbs = S // bl                       # blocks per sequence
        N = B * nbs                         # full worst-case pool
        mesh = make_mesh((dsize, msize), ("data", "model"))
        assert pool_sharding_kind(mesh, N, B) == "2d"
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, 1, H, D)).astype(jnp.bfloat16)
        kn = jax.random.normal(ks[1], (B, 1, K, D)).astype(jnp.bfloat16)
        vn = jax.random.normal(ks[2], (B, 1, K, D)).astype(jnp.bfloat16)
        kp = jax.random.normal(ks[3], (N, bl, K, D)).astype(jnp.bfloat16)
        vp = jax.random.normal(ks[4], (N, bl, K, D)).astype(jnp.bfloat16)
        fn = jax.jit(lambda *a: flash_decode_paged(*a, mesh=mesh))
        row_bytes = 2 * K * D * 2           # k+v rows, bf16
        sub = N // dsize
        for occ in (25, 50, 100):
            n_live = max(1, B * occ // 100)
            pos_np = np.zeros((B,), np.int32)
            pos_np[:n_live] = np.linspace(64, S - 1, n_live) \\
                .astype(np.int32)
            tbl_np = np.full((B, nbs), -1, np.int32)
            used_in = [0] * dsize           # per-sub-pool cursor
            used = 0
            for b in range(n_live):
                g = b * dsize // B          # the slot's data shard
                need = int(np.ceil((pos_np[b] + 1) / bl))
                first = g * sub + used_in[g]
                tbl_np[b, :need] = np.arange(first, first + need)
                used_in[g] += need
                used += need
            tbl = jnp.asarray(tbl_np)
            pos = jnp.asarray(pos_np)
            for _ in range(2):
                jax.block_until_ready(fn(q, kn, vn, kp, vp, tbl, pos, 0))
            ts = []
            for _ in range(10):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, kn, vn, kp, vp, tbl, pos, 0))
                ts.append(time.perf_counter() - t0)
            mib = used * bl * row_bytes / 2**20
            print("ROW=decode_step/paged_2d/occ%d,%.1f,occ=%d%%;live=%d/%d;"
                  "pinned_MiB=%.0f;block_len=%d;blocks=%d/%d;"
                  "pool=2x4 data-major sub-pools, batch partitioned"
                  % (occ, float(np.median(ts)) * 1e6, occ, n_live, B,
                     mib, bl, used, N))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": str(
            Path(__file__).resolve().parents[1] / "src"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    rows = [l[4:] for l in out.stdout.splitlines() if l.startswith("ROW=")]
    if out.returncode == 0 and rows:
        for row in rows:
            name, us, derived = row.split(",", 2)
            emit(name, float(us), derived)
    else:
        emit("decode_step/paged_2d/occ25", 0.0,
             "subprocess failed: " + out.stderr.strip()[-200:])


# ---------------------------------------------------------------------
def table3_end_to_end() -> None:
    from repro.configs import ShapeConfig, get_arch
    from repro.core.pipeline import specialize
    from repro.launch.mesh import make_host_mesh
    from repro.models import synthetic_batch
    from repro.optim import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig

    mesh = make_host_mesh()
    shape = ShapeConfig("bench", "train", 128, 4)
    for name in ("qwen3-8b", "granite-moe-1b-a400m", "mamba2-2.7b",
                 "hymba-1.5b"):
        arch = get_arch(name).reduced()
        plan = specialize(arch, shape, mesh_axes=tuple(mesh.axis_names),
                          mesh_shape=tuple(mesh.devices.shape))
        tr = Trainer(plan, mesh, TrainerConfig(n_steps=1, ckpt_every=0),
                     opt_cfg=OptConfig(total_steps=10),
                     arch=arch, shape=shape)
        state = tr.init_state()
        batch = synthetic_batch(arch, shape, jax.random.PRNGKey(1))
        # non-donating wrapper so the benchmark can reuse inputs
        fn = jax.jit(tr.step_def.fn)
        us = _time(fn, state, batch, n=5)
        toks = shape.tokens / (us / 1e6)
        emit(f"train_step/{name}/reduced", us, f"tok_per_s={toks:.0f}")


# ---------------------------------------------------------------------
def table4_roofline() -> None:
    import json
    results = Path(__file__).resolve().parents[1] / "results" / "dryrun"
    rows = sorted(results.glob("*@16x16.json"))
    if not rows:
        emit("roofline/none", 0.0, "run launch/dryrun first")
        return
    for f in rows:
        d = json.loads(f.read_text())
        if "roofline" not in d:
            continue
        r = d["roofline"]
        emit(f"roofline/{d['arch']}@{d['shape']}",
             r["step_time_s"] * 1e6,
             f"bottleneck={r['bottleneck']};mfu={r['mfu']:.3f};"
             f"compute_s={r['compute_s']:.3f};memory_s={r['memory_s']:.3f};"
             f"collective_s={r['collective_s']:.3f};"
             f"useful={r['useful_ratio']:.2f}")


TABLES = {
    "table1": table1_specialization,
    "table2": table2_kernels,
    "table3": table3_end_to_end,
    "table4": table4_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="also write rows to this path (e.g. "
                         "BENCH_table1.json) for the perf trajectory")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, fn in TABLES.items():
        if args.only and args.only != name:
            continue
        fn()
    if args.json:
        import json
        Path(args.json).write_text(json.dumps(ROWS, indent=2) + "\n")
        print(f"# wrote {len(ROWS)} rows to {args.json}", flush=True)


if __name__ == "__main__":
    main()
