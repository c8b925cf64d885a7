import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import: JAX locks the device count on first use.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:

1. builds the production mesh (16×16 ``("data","model")``; with
   ``--multi_pod`` 2×16×16 ``("pod","data","model")``),
2. builds ShapeDtypeStruct stand-ins (no allocation) for the train state /
   KV cache / batch via ``jax.eval_shape`` + the logical-axis rule table,
3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
4. records ``memory_analysis()``, ``cost_analysis()`` and parsed
   collective bytes into ``results/dryrun/<arch>@<shape>@<mesh>.json``.

Usage::

    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi_pod] [--skip-existing]

Skipped cells (encoder decode, full-attention long_500k) are recorded with
their reason so the roofline table shows the complete 40-cell matrix.
"""
import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as Pspec

from repro.analysis import hlo as hlo_lib
from repro.configs import registry, shapes as shp
from repro.configs.base import RunConfig
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.nn import param as P
from repro.optim import make_optimizer
from repro.sharding import rules as R
from repro.train import state as S
from repro.train.steps import make_serve_step, make_train_step

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _attach(tree_sds, tree_sh):
    return jax.tree.map(
        lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        tree_sds, tree_sh)


#: logical axes of each decode-state leaf, by its dict key (without the
#: optional leading "layers" scan-stacking dim, added by rank delta).
_CACHE_LOGICAL = {
    "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
    "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
    "h": ("batch", "rnn"),
    "conv": ("batch", None, "rnn"),
    "conv_x": ("batch", None, "ssm_inner"),
    "conv_B": ("batch", None, "ssm_state"),
    "conv_C": ("batch", None, "ssm_state"),
    "ssm": ("batch", "ssm_heads", None, "ssm_state"),
}


def _cache_shardings(cache_sds, cfg, mesh):
    rules = R.rules_with(dict(cfg.rules_overrides)
                         | dict(cfg.decode_rules_overrides))

    def _sh(path, sd):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        logical = _CACHE_LOGICAL[key]
        if len(sd.shape) == len(logical) + 1:      # scan-stacked
            logical = ("layers",) + logical
        assert len(logical) == len(sd.shape), (key, sd.shape)
        return NamedSharding(mesh, R.resolve_spec(logical, sd.shape, mesh,
                                                  rules))

    return jax.tree_util.tree_map_with_path(
        _sh, cache_sds,
        is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))


def _compile_cell(cfg, kind: str, mesh, run: RunConfig, global_batch: int,
                  seq_len: int):
    """Lower + compile one step function; returns the compiled artifact."""
    rules = R.rules_with(dict(cfg.rules_overrides))
    with R.axis_rules(mesh, rules):
        if kind in ("train", "prefill"):
            optimizer = make_optimizer(run)
            state_sds = S.abstract_state(cfg, run, optimizer)
            state_sh = S.state_shardings(cfg, run, optimizer, mesh)
            batch_sds = shp.token_batch_shapes(cfg, global_batch, seq_len)
            batch_sh = S.batch_shardings(batch_sds, mesh)
            if kind == "train":
                step = make_train_step(cfg, run, optimizer)
                args = (_attach(state_sds, state_sh),
                        _attach(batch_sds, batch_sh))
                in_sh = (state_sh, batch_sh)
                lowered = jax.jit(step, in_shardings=in_sh,
                                  donate_argnums=(0,)).lower(*args)
                return lowered.compile()
            else:
                # prefill: forward pass only (inference), params in bf16
                params_sds = jax.tree.map(
                    lambda sd: jax.ShapeDtypeStruct(sd.shape, jnp.bfloat16),
                    state_sds["params"])

                def step(params, batch):
                    logits, _ = tfm.forward(params, cfg, batch)
                    return jnp.argmax(logits.astype(jnp.float32), axis=-1)

                args = (_attach(params_sds, state_sh["params"]),
                        _attach(batch_sds, batch_sh))
                in_sh = (state_sh["params"], batch_sh)
            lowered = jax.jit(step, in_shardings=in_sh).lower(*args)
        else:  # decode
            params_sds = jax.tree.map(
                lambda sd: jax.ShapeDtypeStruct(sd.shape, jnp.bfloat16),
                tfm.param_shapes(cfg))
            params_sh = S.state_shardings(cfg, run, make_optimizer(run),
                                          mesh)["params"]
            cache_sds = tfm.abstract_cache(cfg, global_batch, seq_len,
                                           jnp.bfloat16)
            cache_sh = _cache_shardings(cache_sds, cfg, mesh)
            tok_sds = jax.ShapeDtypeStruct((global_batch, 1), jnp.int32)
            tok_sh = NamedSharding(mesh, R.resolve_spec(
                ("batch", None), tok_sds.shape, mesh, rules))
            pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
            pos_sh = NamedSharding(mesh, Pspec())
            step = make_serve_step(cfg)
            lowered = jax.jit(step, in_shardings=(params_sh, cache_sh,
                                                  tok_sh, pos_sh)).lower(
                _attach(params_sds, params_sh),
                _attach(cache_sds, cache_sh),
                _attach(tok_sds, tok_sh), _attach(pos_sds, pos_sh))
        return lowered.compile()


def _cell_costs(compiled) -> dict:
    cost = compiled.cost_analysis()
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(hlo_lib.collective_bytes(compiled.as_text()))}


def corrected_costs(cfg, kind: str, mesh, run: RunConfig, global_batch: int,
                    seq_len: int) -> dict:
    """Loop-corrected per-device costs (see EXPERIMENTS.md §Methodology).

    XLA's ``cost_analysis`` counts while-loop bodies ONCE, so a scanned
    model under-reports FLOPs/bytes by the trip counts.  We reconstruct

        total = F_fixed + K · (F_microbatch + G · F_layer_group)

    from small UNROLLED compiles: A (g=1 groups, k=1 microbatch),
    B (g=2, k=1), and — when grad accumulation is active — C (g=1, k=2):
    F_layer = B−A, F_mb = C−B (or A−F_fixed when K=1), F_fixed = 2A−C.
    """
    lp = len(cfg.pattern)
    rem = cfg.n_layers - (cfg.n_layers // lp) * lp
    groups = cfg.n_layers // lp
    k_prod = run.grad_accum if kind == "train" else 1
    mb = global_batch // k_prod

    def variant(g, k, batch):
        vcfg = dataclasses.replace(cfg, n_layers=lp * g + rem,
                                   scan_layers=False)
        vrun = dataclasses.replace(run, grad_accum=k, accum_unroll=True)
        comp = _compile_cell(vcfg, kind, mesh, vrun, batch, seq_len)
        return _cell_costs(comp)

    a = variant(1, 1, mb)
    b = variant(2, 1, mb)
    out = {}
    if kind == "train" and k_prod > 1:
        c = variant(1, 2, 2 * mb)
        for key in ("flops", "bytes", "coll"):
            f_layer = b[key] - a[key]
            f_mb = c[key] - b[key]
            f_fixed = 2 * a[key] - c[key]
            out[key] = f_fixed + k_prod * (f_mb + groups * f_layer)
    else:
        for key in ("flops", "bytes", "coll"):
            f_layer = b[key] - a[key]
            f_fixed = a[key] - f_layer
            out[key] = f_fixed + groups * f_layer
    return out


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool = False,
                run: RunConfig | None = None, verbose: bool = True,
                mesh=None, cfg=None, correct_costs: bool = True) -> dict:
    """Lower+compile one cell; returns the result record (also JSON'd).

    ``mesh``/``cfg`` overrides exist for tests (reduced meshes/configs) and
    for the perf hillclimb (modified configs on the production mesh).
    """
    cfg = cfg or registry.get_config(arch)
    shape = shp.SHAPES[shape_name]
    mesh_name = ("x".join(str(s) for s in mesh.devices.shape) if mesh is not
                 None else ("2x16x16" if multi_pod else "16x16"))
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "kind": shape.kind}

    runnable, reason = shp.cell_status(cfg, shape_name)
    if not runnable:
        record.update(status="skipped", reason=reason)
        return record

    run = run or RunConfig(grad_accum=8 if shape.kind == "train" else 1)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()

    compiled = _compile_cell(cfg, shape.kind, mesh, run,
                             shape.global_batch, shape.seq_len)
    compile_s = time.time() - t0

    if True:
        mem = compiled.memory_analysis()
        n_params = tfm.count_params(cfg)
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.kind != "decode" else 1)
        active = None
        if cfg.moe_experts > 0:
            # active params: replace expert count with top_k in MoE blocks
            dense_like = tfm.count_params(cfg) - _moe_param_delta(cfg)
            active = dense_like
        mf = hlo_lib.model_flops_per_step(
            n_params, tokens, "train" if shape.kind == "train" else "serve",
            active_params=active)
        coll = hlo_lib.collective_stats(compiled.as_text())

        if correct_costs:
            costs = corrected_costs(cfg, shape.kind, mesh, run,
                                    shape.global_batch, shape.seq_len)
        else:
            costs = _cell_costs(compiled)
        roof = hlo_lib.Roofline(costs["flops"], costs["bytes"],
                                costs["coll"], chips, mf)

        record.update(
            status="ok", compile_s=round(compile_s, 1), chips=chips,
            n_params=n_params, tokens_per_step=tokens,
            grad_accum=run.grad_accum, cost_corrected=bool(correct_costs),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "total_nonalias_bytes": (mem.argument_size_in_bytes
                                         + mem.output_size_in_bytes
                                         + mem.temp_size_in_bytes
                                         - mem.alias_size_in_bytes),
            },
            roofline=roof.as_dict(),
            collectives={k: v for k, v in coll.items() if v["count"]},
        )
        if verbose:
            ma = record["memory"]
            print(f"  mem/dev: args={ma['argument_bytes']/2**30:.2f}GiB "
                  f"temp={ma['temp_bytes']/2**30:.2f}GiB | "
                  f"compute={roof.compute_s*1e3:.1f}ms "
                  f"memory={roof.memory_s*1e3:.1f}ms "
                  f"coll={roof.collective_s*1e3:.1f}ms "
                  f"-> {roof.dominant}-bound (compile {compile_s:.0f}s)")
    return record


def _moe_param_delta(cfg) -> int:
    """Params in inactive experts (for 6·N_active·D)."""
    if cfg.moe_experts == 0:
        return 0
    glu = 3 if cfg.mlp in ("swiglu", "geglu") else 2
    per_expert = glu * cfg.d_model * cfg.d_ff
    return (cfg.moe_experts - cfg.moe_top_k) * per_expert * cfg.n_layers


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(shp.SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi_pod", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-correct", action="store_true",
                    help="skip the loop-correction compiles (multi-pod "
                         "compile-proof pass; roofline comes from the "
                         "single-pod run)")
    args = ap.parse_args(argv)

    archs = registry.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes_ = list(shp.SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    RESULTS.mkdir(parents=True, exist_ok=True)

    failures = []
    for arch in archs:
        for shape_name in shapes_:
            mesh_name = "2x16x16" if args.multi_pod else "16x16"
            out = RESULTS / f"{arch}@{shape_name}@{mesh_name}.json"
            if args.skip_existing and out.exists():
                print(f"[skip-existing] {arch} × {shape_name} × {mesh_name}")
                continue
            print(f"[dryrun] {arch} × {shape_name} × {mesh_name}")
            try:
                rec = dryrun_cell(arch, shape_name, args.multi_pod,
                                  correct_costs=not args.no_correct)
            except Exception as e:  # noqa: BLE001 — record and continue
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                       "status": "error", "error": f"{type(e).__name__}: {e}"}
                failures.append((arch, shape_name))
            out.write_text(json.dumps(rec, indent=2, default=float))
            if rec["status"] == "skipped":
                print(f"  skipped: {rec['reason']}")
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
