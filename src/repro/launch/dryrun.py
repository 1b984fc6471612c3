import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST be the first lines: jax locks the platform and device count on
# first init, and the 512 forced host devices are CPU devices.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script builds the real step function (train / prefill /
serve), assigns the production shardings from parallel.sharding, lowers it
against ShapeDtypeStruct inputs (no allocation), compiles it for the
production mesh and extracts:

  * memory_analysis()      -> bytes/device (proves the cell fits HBM)
  * cost_analysis()        -> HLO FLOPs / HLO bytes (roofline compute+memory)
  * the partitioned HLO    -> per-kind collective byte counts (roofline
                              collective term; parsed from as_text())

Results are cached as JSON under benchmarks/out/dryrun/ — one file per
(arch, shape, mesh, variant) — and consumed by benchmarks/roofline.py and
EXPERIMENTS.md. (No ``from __future__`` here: the XLA_FLAGS lines above
must stay the first statements in the file.)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
      --shape train_4k --mesh pod --variant baseline
  PYTHONPATH=src python -m repro.launch.dryrun --list
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path


import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import hw
from repro.core.apelink import protocol_efficiency
from repro.launch import hlo_analysis
from repro.models import api
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.parallel import sharding

OUT_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "out" / "dryrun"

MESHES = {
    "pod": dict(multi_pod=False, chips=256),
    "multipod": dict(multi_pod=True, chips=512),
}

# ----------------------------------------------------------------------------
# variants (perf hillclimbing) — "baseline" is the paper-faithful default
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Variant:
    name: str = "baseline"
    remat: bool = True           # activation checkpointing in train_loss
    donate: bool = True          # donate params/opt buffers (in-place update)
    # pin jit out_shardings to the in_shardings (params/opt state keep
    # their layout through the update — stops the partitioner from
    # round-tripping f32 full-weight copies; §Perf "outsharded")
    out_shardings: bool = False
    # microbatch gradient accumulation (activation memory / overlap knob)
    grad_accum: int = 1
    # ArchCfg field overrides (dataclasses.replace) — the hillclimb knobs
    cfg_overrides: tuple = ()    # (("field", value), ...)
    extra: dict | None = None    # free-form notes, recorded in the JSON


_FAITHFUL = (("scan_impl", "pertoken"), ("moe_impl", "global"),
             ("tp_activations", "free"), ("parallelism", "tp_dp"),
             ("attn_dtype", "f32"))

VARIANTS: dict[str, Variant] = {
    # the paper-faithful baseline pins every §Perf knob to the naive
    # setting (sequential scans, global MoE dispatch, free activation
    # sharding, TPxDP for all archs, f32 attention) — matches the
    # recorded baseline sweep regardless of the per-arch config defaults
    "baseline": Variant(cfg_overrides=_FAITHFUL),
    # per-arch production defaults (the optimized configuration each
    # config file ships with; see EXPERIMENTS.md §Perf)
    "production": Variant(name="production", out_shardings=True),
    "noremat": Variant(name="noremat", remat=False,
                       cfg_overrides=_FAITHFUL),
    "nodonate": Variant(name="nodonate", donate=False,
                        cfg_overrides=_FAITHFUL),
    # §Perf hillclimb variants
    "chunked_ssm": Variant(name="chunked_ssm",
                           cfg_overrides=(("scan_impl", "chunked"),)),
    "ep_a2a": Variant(name="ep_a2a",
                      cfg_overrides=(("moe_impl", "ep_a2a"),)),
    "tp_megatron": Variant(name="tp_megatron",
                           cfg_overrides=(("tp_activations", "megatron"),)),
    "tp_sp": Variant(name="tp_sp",
                     cfg_overrides=(("tp_activations", "sp"),)),
    "ep_a2a_megatron": Variant(
        name="ep_a2a_megatron",
        cfg_overrides=(("moe_impl", "ep_a2a"),
                       ("tp_activations", "megatron"))),
    "dp_only": Variant(name="dp_only",
                       cfg_overrides=(("parallelism", "dp_only"),)),
    # attribution singles
    "attn_bf16": Variant(name="attn_bf16",
                         cfg_overrides=(("attn_dtype", "bf16"),)),
    "outsharded": Variant(name="outsharded", out_shardings=True),
    # combined per-cell winners (§Perf)
    "sp_fast": Variant(name="sp_fast", out_shardings=True,
                       cfg_overrides=(("tp_activations", "sp"),
                                      ("attn_dtype", "bf16"))),
    "ep_fast": Variant(name="ep_fast", out_shardings=True,
                       cfg_overrides=(("moe_impl", "ep_a2a"),
                                      ("attn_dtype", "bf16"))),
    "ssm_fast": Variant(name="ssm_fast", out_shardings=True,
                        cfg_overrides=(("scan_impl", "chunked"),
                                       ("attn_dtype", "bf16"))),
    "dp_fast": Variant(name="dp_fast", out_shardings=True,
                       cfg_overrides=(("parallelism", "dp_only"),
                                      ("attn_dtype", "bf16"))),
    # microbatch gradient accumulation (activation memory knob)
    "accum4": Variant(name="accum4", grad_accum=4),
    "accum8": Variant(name="accum8", grad_accum=8),
    # hand-SPMD Megatron-SP dense layer (explicit bf16 AG/RS in shard_map)
    "manual_sp": Variant(name="manual_sp",
                         cfg_overrides=(("tp_activations", "manual_sp"),)),
    "manual_sp_bf16": Variant(
        name="manual_sp_bf16",
        cfg_overrides=(("tp_activations", "manual_sp"),
                       ("attn_dtype", "bf16"))),
}


def get_variant(name: str) -> Variant:
    return VARIANTS[name]


def apply_variant(cfg, variant: Variant):
    if not variant.cfg_overrides:
        return cfg
    return dataclasses.replace(cfg, **dict(variant.cfg_overrides))


# ----------------------------------------------------------------------------
# useful attention flops (causal-masked QK^T + AV, one forward pass)
# ----------------------------------------------------------------------------


def model_attn_flops(cfg, shape, *, decode: bool = False) -> float:
    """Useful attention-matmul FLOPs for one forward pass (global).

    Causal attention does 2*0.5*S^2*H*hd flops for each of QK^T and AV per
    sequence; a decode step attends one query against a seq_len cache.
    Recurrent families (rwkv6, mamba2) have no S^2 term; zamba2 has one
    shared attention block applied every ``attn_every`` mamba layers;
    whisper adds the non-causal encoder and cross-attention.
    """
    B, S = shape.global_batch, shape.seq_len
    hd = cfg.resolved_head_dim
    H = max(cfg.n_heads, 1)

    def causal(n_layers, s):
        per_seq = 2 * 0.5 * s * s * H * hd * 2  # QK + AV, causal half
        return n_layers * B * per_seq

    def one_step(n_layers, cache):
        return n_layers * B * (2 * cache * H * hd * 2)

    fam = cfg.family
    if fam in ("rwkv6", "mamba2"):
        return 0.0
    if fam == "zamba2":
        n_attn = max(cfg.n_layers // max(cfg.attn_every, 1), 1)
        return one_step(n_attn, S) if decode else causal(n_attn, S)
    if fam == "encdec":
        enc = cfg.n_enc_layers * B * (2 * cfg.n_frames ** 2 * H * hd * 2)
        if decode:
            dec = one_step(cfg.n_layers, S)
            cross = cfg.n_layers * B * (2 * cfg.n_frames * H * hd * 2)
            return dec + cross  # encoder ran at prefill
        dec = causal(cfg.n_layers, S)
        cross = cfg.n_layers * B * (2 * S * cfg.n_frames * H * hd * 2)
        return enc + dec + cross
    # dense / moe / vlm decoder stacks
    s_eff = S + (cfg.n_patches if fam == "vlm" else 0)
    if decode:
        return one_step(cfg.n_layers, s_eff)
    return causal(cfg.n_layers, s_eff)


# ----------------------------------------------------------------------------
# step builders: (jitted_fn, arg_specs_with_shardings)
# ----------------------------------------------------------------------------


def build_train(cfg, mesh, variant: Variant):
    model = api.get_model(cfg)
    shapes = api.param_shapes(cfg)
    psh = sharding.named(mesh, sharding.param_specs(cfg, shapes, mesh))
    ost_shapes = jax.eval_shape(adamw_init, shapes)
    osp = {"m": sharding.zero1_specs(cfg, shapes, mesh),
           "v": sharding.zero1_specs(cfg, shapes, mesh), "step": P()}
    osh = sharding.named(mesh, osp)
    opt = AdamWConfig()
    remat = variant.remat
    accum = variant.grad_accum

    def specs(shape_name):
        shape, batch = api.input_specs(cfg, shape_name)
        bspecs = sharding.batch_specs(cfg, batch, mesh)
        bsh = sharding.named(mesh, bspecs)
        # the (accum, B/accum, ...) reshape must keep the DP sharding on
        # the per-microbatch dim (dim 1) — left free, GSPMD replicates
        micro_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, P(None, *s)), bspecs)

        def single(p, b):
            return jax.value_and_grad(
                lambda q: model.train_loss(q, b, remat=remat))(p)

        def loss_and_grads(params, batch):
            if accum <= 1:
                return single(params, batch)
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)
            micro = jax.lax.with_sharding_constraint(micro, micro_sh)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)

            def body(carry, mb):
                la, ga = carry
                loss, g = single(params, mb)
                return (la + loss, jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), ga, g)), None

            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro)
            inv = 1.0 / accum
            return loss * inv, jax.tree.map(lambda g: g * inv, grads)

        def train_step(params, opt_state, batch):
            loss, grads = loss_and_grads(params, batch)
            params, opt_state, metrics = adamw_update(opt, grads, opt_state,
                                                      params)
            return params, opt_state, {"loss": loss, **metrics}

        in_sh = (psh, osh, bsh)
        donate = (0, 1) if variant.donate else ()
        kw = {}
        if variant.out_shardings:
            kw["out_shardings"] = (psh, osh, None)
        fn = jax.jit(train_step, in_shardings=in_sh,
                     donate_argnums=donate, **kw)
        args = (shapes, ost_shapes, batch)
        return fn, args

    return specs


def build_prefill(cfg, mesh, variant: Variant):
    model = api.get_model(cfg)
    shapes = api.param_shapes(cfg)
    psh = sharding.named(mesh, sharding.param_specs(cfg, shapes, mesh))

    def specs(shape_name):
        shape, batch = api.input_specs(cfg, shape_name)
        bsh = sharding.named(mesh, sharding.batch_specs(cfg, batch, mesh))

        def prefill_step(params, batch):
            return model.prefill(params, batch, remat=False) \
                if cfg.family in ("rwkv6", "mamba2") else \
                model.prefill(params, batch, max_len=shape.seq_len,
                              remat=False)

        fn = jax.jit(prefill_step, in_shardings=(psh, bsh))
        return fn, (shapes, batch)

    return specs


def build_decode(cfg, mesh, variant: Variant):
    # decode is weight-read-bound: replicating params (dp_only) doubles the
    # per-step HBM traffic (measured on starcoder2 decode_32k), so serving
    # always uses TP-sharded params even for dp_only-trained archs
    if cfg.parallelism == "dp_only":
        cfg = dataclasses.replace(cfg, parallelism="tp_dp")
    model = api.get_model(cfg)
    shapes = api.param_shapes(cfg)
    psh = sharding.named(mesh, sharding.param_specs(cfg, shapes, mesh))

    def specs(shape_name):
        shape, spec = api.input_specs(cfg, shape_name)
        tok_sh = sharding.named(
            mesh, sharding.batch_specs(cfg, {"t": spec["token"]}, mesh))["t"]
        st_sh = sharding.named(mesh, sharding.decode_state_specs(
            cfg, spec["state"], mesh, shape.global_batch))
        pos_sh = NamedSharding(mesh, P())

        def serve_step(params, token, state, pos):
            return model.decode_step(params, token, state, pos)

        fn = jax.jit(serve_step, in_shardings=(psh, tok_sh, st_sh, pos_sh),
                     donate_argnums=(2,))
        return fn, (shapes, spec["token"], spec["state"], spec["pos"])

    return specs


def build_cell(cfg, mesh, shape_name: str, variant: Variant):
    kind = api.SHAPES[shape_name].kind
    builder = {"train": build_train, "prefill": build_prefill,
               "decode": build_decode}[kind]
    return builder(cfg, mesh, variant)(shape_name)


# ----------------------------------------------------------------------------
# per-cell dry run
# ----------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_name: str,
             variant: Variant) -> dict:
    from repro.launch.mesh import make_production_mesh
    cfg = apply_variant(configs.get_config(arch), variant)
    chips = MESHES[mesh_name]["chips"]
    mesh = make_production_mesh(multi_pod=MESHES[mesh_name]["multi_pod"])
    t0 = time.time()
    try:
        sharding.set_runtime_mesh(mesh)
        with mesh:
            fn, args = build_cell(cfg, mesh, shape_name, variant)
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
    finally:
        sharding.set_runtime_mesh(None)

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older JAX returns [dict]
        cost = cost[0] if cost else {}
    cost = {k: v for k, v in cost.items()
            if k in ("flops", "bytes accessed", "transcendentals",
                     "optimal_seconds")}
    try:
        mem = compiled.memory_analysis()
        mem_d = {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes") if hasattr(mem, k)}
        # live bytes/device ~ args + outputs + temps - aliased(donated)
        live = (mem_d.get("argument_size_in_bytes", 0)
                + mem_d.get("output_size_in_bytes", 0)
                + mem_d.get("temp_size_in_bytes", 0)
                - mem_d.get("alias_size_in_bytes", 0))
        mem_d["live_bytes_per_device"] = live
        mem_d["fits_hbm"] = bool(live <= hw.TPU_V5E.hbm_bytes)
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)}
    hlo = compiled.as_text()
    # trip-count-aware analysis (XLA's cost_analysis counts scan bodies once)
    ana = hlo_analysis.analyze(hlo)
    colls = ana.collectives
    link_bytes = ana.link_bytes

    chip = hw.TPU_V5E
    flops_dev = float(ana.flops)
    bytes_dev = float(ana.bytes)
    eta = protocol_efficiency()  # APElink-style link derate (paper §2.3)
    terms = {
        "compute_s": flops_dev / chip.peak_flops_bf16,
        "memory_s": bytes_dev / chip.hbm_bandwidth,
        "collective_s": link_bytes / chip.ici_link_bandwidth,
        "collective_derated_s":
            link_bytes / (chip.ici_link_bandwidth * eta),
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])

    # model FLOPs: 6*N_active*D for train (fwd+bwd), 2*N_active*D for
    # inference, per chip; the _attn variant adds the useful causal
    # attention-matmul flops (QK^T + AV), which dominate small-d_model
    # archs at seq 4096+ and are invisible to the parameter-count formula
    n_active = api.active_param_count(cfg)
    shape = api.SHAPES[shape_name]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
        attn_flops = 3.0 * model_attn_flops(cfg, shape)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
        attn_flops = model_attn_flops(cfg, shape)
    else:  # decode: one token per sequence against a seq_len cache
        model_flops = 2.0 * n_active * shape.global_batch
        attn_flops = model_attn_flops(cfg, shape, decode=True)
    model_flops_dev = model_flops / chips
    attn_flops_dev = attn_flops / chips

    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "variant": variant.name, "chips": chips,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem_d,
        "collectives": colls,
        "top_collective_buffers": ana.top_buffers(12),
        "link_bytes_per_device": link_bytes,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "n_while": ana.n_while,
        "max_trip": ana.max_trip,
        "model_flops_per_device": model_flops_dev,
        "attn_model_flops_per_device": attn_flops_dev,
        "useful_flop_ratio":
            model_flops_dev / flops_dev if flops_dev else None,
        "useful_flop_ratio_attn":
            (model_flops_dev + attn_flops_dev) / flops_dev
            if flops_dev else None,
        "roofline": terms,
        "n_params": api.param_count(cfg),
        "n_active_params": n_active,
        "hlo_bytes": len(hlo),
    }
    return out


def cell_path(arch, shape, mesh_name, variant, out_dir=None) -> Path:
    v = "" if variant == "baseline" else f"_{variant}"
    return (out_dir or OUT_DIR) / f"{arch}_{shape}_{mesh_name}{v}.json"


def all_cells(archs, shapes_filter, mesh_names):
    for arch in archs:
        cfg = configs.get_config(arch)
        for shape in api.applicable_shapes(cfg):
            if shapes_filter and shape not in shapes_filter:
                continue
            for mesh_name in mesh_names:
                yield arch, shape, mesh_name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="both")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=None, help="output dir override")
    args = ap.parse_args(argv)
    out_dir = Path(args.out) if args.out else OUT_DIR

    archs = [configs.canonical(a) for a in (args.arch or configs.ALL_ARCHS)]
    mesh_names = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    variant = get_variant(args.variant)
    cells = list(all_cells(archs, args.shape, mesh_names))
    if args.list:
        for c in cells:
            print(*c)
        print(f"{len(cells)} cells")
        return 0

    out_dir.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch, shape, mesh_name in cells:
        path = cell_path(arch, shape, mesh_name, variant.name, out_dir)
        if path.exists() and not args.force:
            print(f"[skip] {path.name}")
            continue
        print(f"[cell] {arch} x {shape} x {mesh_name} ({variant.name}) ...",
              flush=True)
        try:
            out = run_cell(arch, shape, mesh_name, variant)
        except Exception:
            traceback.print_exc()
            failures.append((arch, shape, mesh_name))
            continue
        path.write_text(json.dumps(out, indent=1))
        r = out["roofline"]
        print(f"   ok: compile {out['t_compile_s']}s  "
              f"flops/dev {out['flops_per_device']:.3e}  "
              f"bytes/dev {out['bytes_per_device']:.3e}  "
              f"link/dev {out['link_bytes_per_device']:.3e}  "
              f"bottleneck {r['bottleneck']}", flush=True)
    if failures:
        print("FAILED CELLS:", failures)
        return 1
    print("all requested cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
