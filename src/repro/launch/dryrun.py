"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 host-platform placeholder devices stand in for 2 TPU v5e
pods; ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed
for the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh for every
cell, and the compiled artifact yields the roofline terms
(cost_analysis + collective bytes parsed from the partitioned HLO).

Usage:
    python -m repro.launch.dryrun --arch starcoder2-7b --shape train_4k
    python -m repro.launch.dryrun --arch ... --shape ... --multipod
    python -m repro.launch.dryrun --all            # every runnable cell,
                                                   # one subprocess per cell
Outputs one JSON per cell under --out (default experiments/dryrun/).

It is a CPU stand-in: ``main`` pins this process and the children it
starts to the CPU backend with 512 host devices, so it never takes a TPU
that a child would then need.
"""
import argparse
import gc
import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.core.policy import QuantPolicy
from repro.launch import hlo_cost
from repro.launch import mesh as mesh_mod
from repro.models import model
from repro.optim import adamw, sgdm
from repro.optim.schedules import cosine
from repro.runtime import sharding, steps

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _tensor_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo: str) -> dict:
    """Sum operand bytes of every collective op in the (post-partitioning)
    HLO.  Shapes in the partitioned module are PER-DEVICE shard shapes, so
    the totals are per-chip wire bytes."""
    out = {k: {"ops": 0, "operand_bytes": 0} for k in _COLLECTIVES}
    for line in hlo.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+)$", s)
        if not m:
            continue
        rhs = m.group(1)
        for kind in _COLLECTIVES:
            # match `<result-shape> all-reduce(` and async `-start(` forms;
            # skip `-done` (would double count).
            km = re.search(rf"\b{kind}(-start)?\(", rhs)
            if not km:
                continue
            args = rhs[km.end():]
            depth = 1
            end = 0
            for i, ch in enumerate(args):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            operand_str = args[:end]
            b = sum(_tensor_bytes(d, dims)
                    for d, dims in _SHAPE_RE.findall(operand_str))
            out[kind]["ops"] += 1
            out[kind]["operand_bytes"] += b
            break
    out["total_operand_bytes"] = sum(
        v["operand_bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_ops"] = sum(
        v["ops"] for k, v in out.items() if isinstance(v, dict))
    return out


def count_params(shapes_tree) -> int:
    return int(sum(np.prod(l.shape) for l in
                   jax.tree_util.tree_leaves(shapes_tree)))


def moe_inactive_params(cfg, params_shapes) -> int:
    """Parameters in routed experts that a single token does NOT touch."""
    if cfg.moe is None:
        return 0
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shapes)[0]:
        p = "/".join(str(getattr(x, "key", x)) for x in path)
        if "/moe/" in p and "shared" not in p and \
                p.rsplit("/", 1)[-1] in ("w_up", "w_gate", "w_down"):
            total += int(np.prod(leaf.shape))
    frac = 1.0 - cfg.moe.top_k / cfg.moe.n_experts
    return int(total * frac)


def build_cell(cfg, shape, mesh, multi_pod: bool, policy: QuantPolicy,
               fsdp: str = "2d", grad_accum=None):
    """Returns (jitted_fn, example_args, donate) ready to lower."""
    dp = mesh_mod.dp_axes(multi_pod)
    ispecs = configs.input_specs(cfg, shape)

    def nm(pspecs):
        return sharding.named(pspecs, mesh)

    def adjust(pspec_tree):
        if fsdp == "2d":
            return pspec_tree
        # tp-only: drop the fsdp ("data") axis from parameter specs.
        from jax.sharding import PartitionSpec as P

        def fix(s):
            return P(*[None if a == "data" else a for a in s])
        return jax.tree_util.tree_map(fix, pspec_tree,
                                      is_leaf=lambda x: isinstance(
                                          x, jax.sharding.PartitionSpec))

    if shape.kind == "train":
        opt = sgdm(momentum=0.9) if cfg.optimizer == "sgdm" else adamw()
        accum = grad_accum or cfg.grad_accum_for(shape.name)
        state_sds = jax.eval_shape(
            lambda k: steps.init_train_state(k, cfg, opt),
            jax.random.PRNGKey(0))
        fn = steps.make_train_step(
            cfg, policy, opt, cosine(3e-4, 10000, warmup=100),
            grad_accum=accum)
        st_specs = sharding.train_state_pspecs(state_sds, mesh)
        st_specs["params"] = adjust(st_specs["params"])
        st_specs["opt"] = adjust(st_specs["opt"])
        in_sh = (nm(st_specs), nm(sharding.batch_pspecs(ispecs, mesh, dp)))
        out_sh = (nm(st_specs), None)
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=(0,))
        return jfn, (state_sds, ispecs)

    params_sds = jax.eval_shape(lambda k: model.init_params(k, cfg),
                                jax.random.PRNGKey(0))
    quant_sds = jax.eval_shape(lambda: model.init_quant_state(cfg))
    p_specs = adjust(sharding.param_pspecs(params_sds, mesh))
    q_specs = sharding.replicated_pspecs(quant_sds)

    if shape.kind == "prefill":
        fn = steps.make_prefill_step(cfg, policy, cache_len=shape.seq_len)
        in_sh = (nm(p_specs), nm(q_specs),
                 nm(sharding.batch_pspecs(ispecs, mesh, dp)))
        jfn = jax.jit(fn, in_shardings=in_sh)
        return jfn, (params_sds, quant_sds, ispecs)

    # decode
    b = shape.global_batch
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(cfg, b, shape.seq_len))
    fn = steps.make_decode_step(cfg, policy)
    c_specs = {"decoder": sharding.cache_pspecs(cache_sds["decoder"], mesh, dp)}
    in_sh = (nm(p_specs), nm(q_specs),
             nm(sharding.batch_pspecs(ispecs, mesh, dp)), nm(c_specs))
    jfn = jax.jit(fn, in_shardings=in_sh, donate_argnums=(3,))
    return jfn, (params_sds, quant_sds, ispecs, cache_sds)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             policy_kind: str = "hindsight", fsdp: str = "2d",
             grad_accum=None, tag: str = "", seq_shard: bool = False,
             int8_gather: bool = False) -> dict:
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    ok, why = cfg.supports(shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "policy": policy_kind, "fsdp": fsdp, "tag": tag,
           "seq_shard": seq_shard, "grad_accum_override": grad_accum}
    if not ok:
        rec.update(status="skipped", reason=why)
        return _write(rec, out_dir)

    if policy_kind == "fp32":
        policy = QuantPolicy.disabled()
    else:
        policy = QuantPolicy.w8a8g8(act_kind=policy_kind,
                                    grad_kind=policy_kind)
    if int8_gather:
        import dataclasses
        policy = dataclasses.replace(policy, int8_weight_gather=True)

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    dp = mesh_mod.dp_axes(multi_pod)
    hints = {"batch": dp if len(dp) > 1 else dp[0],
             "seq": "model" if seq_shard else None,
             "embed": None, "model": "model",
             "model_size": mesh.shape["model"]}

    t0 = time.time()
    with mesh, sharding.activation_hints(hints):
        jfn, args = build_cell(cfg, shape, mesh, multi_pod, policy,
                               fsdp=fsdp, grad_accum=grad_accum)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        host_total = (rec["memory"].get("argument_size_in_bytes", 0)
                      + rec["memory"].get("temp_size_in_bytes", 0)
                      + rec["memory"].get("output_size_in_bytes", 0)
                      - rec["memory"].get("alias_size_in_bytes", 0))
        rec["memory"]["per_device_bytes_est"] = int(host_total)
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    try:
        ca = compiled.cost_analysis()
        # NOTE: xla's cost_analysis counts while bodies ONCE — kept for
        # reference only; the roofline uses the trip-count-aware analyzer.
        rec["cost_xla_raw"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
    except Exception as e:  # pragma: no cover
        rec["cost_xla_raw"] = {"error": str(e)}

    hlo = compiled.as_text()
    rec["hlo_lines"] = hlo.count("\n")
    cost = hlo_cost.analyze(hlo)
    rec["cost"] = {"flops": cost["flops"],
                   "bytes_accessed": cost["bytes_accessed"],
                   "transcendentals": cost["transcendentals"]}
    rec["collectives"] = {
        k: v for k, v in cost["collectives"].items()}
    rec["collectives"]["total_operand_bytes"] = \
        cost["collective_operand_bytes"]
    rec["collectives"]["total_ops"] = cost["collective_ops"]

    # model-level FLOPs for the usefulness ratio.
    params_sds = jax.eval_shape(lambda k: model.init_params(k, cfg),
                                jax.random.PRNGKey(0))
    n_params = count_params(params_sds)
    n_active = n_params - moe_inactive_params(cfg, params_sds)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    factor = 6 if shape.kind == "train" else 2
    rec["model"] = {
        "n_params": n_params, "n_active_params": n_active,
        "tokens_per_step": tokens,
        "model_flops": float(factor * n_active * tokens),
    }

    rec.update(status="ok", lower_s=round(t_lower, 2),
               compile_s=round(t_compile, 2))
    del compiled, lowered, jfn
    gc.collect()
    return _write(rec, out_dir)


def _write(rec: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    name = (f"{rec['arch']}__{rec['shape']}__{rec['mesh'].replace('x', '_')}"
            + (f"__{rec['tag']}" if rec.get("tag") else "") + ".json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="with --all: run single-pod AND multi-pod")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--policy", default="hindsight",
                    choices=["hindsight", "current", "running", "fp32"])
    ap.add_argument("--fsdp", default="2d", choices=["2d", "tp"])
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--int8-gather", action="store_true",
                    help="pin FSDP weight all-gathers to the int8 tensor")
    ap.add_argument("--seq-shard", action="store_true",
                    help="Megatron-SP: shard the residual stream's sequence "
                         "dim over the model axis (activation memory /16)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()

    # Before the first backend use; the environment reaches the children.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    # Stochastic-rounding noise must be generated SHARDED: partitionable
    # threefry lets GSPMD split the bit generation with the consuming
    # tensor (the rbg RngBitGenerator alternative is not partitionable and
    # replicates the noise).
    jax.config.update("jax_threefry_partitionable", True)

    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multipod]
        failures = []
        for cell in configs.cells():
            for mp in meshes:
                if not cell.runnable:
                    run_cell(cell.arch, cell.shape, mp, args.out)
                    print(f"SKIP  {cell.arch} {cell.shape} "
                          f"{'mp' if mp else 'sp'}: {cell.skip_reason}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", cell.arch, "--shape", cell.shape,
                       "--out", args.out, "--policy", args.policy,
                       "--fsdp", args.fsdp, "--tag", args.tag]
                if mp:
                    cmd.append("--multipod")
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=args.timeout)
                status = "ok" if r.returncode == 0 else "FAIL"
                print(f"{status:5s} {cell.arch:24s} {cell.shape:12s} "
                      f"{'mp' if mp else 'sp'} {time.time()-t0:7.1f}s")
                if r.returncode != 0:
                    failures.append((cell.arch, cell.shape, mp))
                    print(r.stderr[-2000:])
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        return

    rec = run_cell(args.arch, args.shape, args.multipod, args.out,
                   policy_kind=args.policy, fsdp=args.fsdp,
                   grad_accum=args.grad_accum, tag=args.tag,
                   seq_shard=args.seq_shard, int8_gather=args.int8_gather)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
