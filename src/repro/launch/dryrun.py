"""Multi-pod dry-run driver (assignment spec, MULTI-POD DRY-RUN §3).

For every (architecture x input-shape x mesh) cell:
  lower the step function with ShapeDtypeStruct inputs + explicit
  in/out shardings -> compile -> record memory_analysis / cost_analysis /
  HLO collective traffic into artifacts/dryrun/<cell>.json.

`--mesh single` = (data=16, model=16) v5e-256 pod;
`--mesh multi`  = (pod=2, data=16, model=16) 512 chips.

`main()` simulates the 512 host devices before anything touches a backend;
importing this module starts none.
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, reduced_config, shape_applicable
from repro.distributed.sharding import make_runtime
from repro.launch import specs as S
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models import lm
from repro.models.init import abstract_params
from repro.serve.step import build_decode_step, build_prefill_step
from repro.train.optimizer import adamw_init
from repro.train.step import build_train_step


def _rep(mesh):
    return NamedSharding(mesh, P())


def _logits_sh(rt, batch):
    dp = rt.batch_axes if len(rt.batch_axes) > 1 else rt.batch_axes[0]
    if batch == 1:
        return NamedSharding(rt.mesh, P(None, "model"))
    return NamedSharding(rt.mesh, P(dp, "model"))


def build_lowering(arch: str, shape: str, mesh, *, reduced: bool = False,
                   overrides: dict | None = None):
    """Returns (lowered, meta) for the cell."""
    cfg = reduced_config(arch) if reduced else get_config(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    sh = dict(SHAPES[shape])
    if reduced:       # tiny shapes for the in-CI smoke path
        sh.update(seq_len=max(256, sh["seq_len"] // 128),
                  global_batch=max(4, sh["global_batch"] // 64))
    b, s, kind = sh["global_batch"], sh["seq_len"], sh["kind"]
    rt = make_runtime(mesh)
    params_abs = abstract_params(cfg)
    p_sh = S.param_shardings_abstract(rt, params_abs)

    d = cfg.d_model
    if kind == "train":
        data = _train_inputs(cfg, b, s)
        data_sh = S.data_shardings(rt, data, kind=kind)
        opt_abs = jax.eval_shape(
            lambda p: adamw_init(p, cfg.opt_state_dtype), params_abs)
        opt_sh = S.opt_state_shardings(rt, p_sh)
        step = build_train_step(cfg, rt)
        metrics_sh = {"loss": _rep(mesh), "grad_norm": _rep(mesh),
                      "lr": _rep(mesh), "step": _rep(mesh)}
        jitted = jax.jit(step,
                         in_shardings=(p_sh, opt_sh, data_sh),
                         out_shardings=(p_sh, opt_sh, metrics_sh),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_abs, opt_abs, data)
    elif kind == "prefill":
        data = S.input_specs(arch, shape) if not reduced else _prefill_inputs(cfg, b, s)
        data_sh = S.data_shardings(rt, data, kind=kind)
        caches_abs = S.cache_specs(cfg, b, _prefill_cache_len(cfg, s))
        caches_sh = S.cache_shardings(rt, cfg, caches_abs, batch=b)
        dp = rt.batch_axes if len(rt.batch_axes) > 1 else rt.batch_axes[0]
        pos_sh = NamedSharding(mesh, P(dp if b > 1 else None))
        step = build_prefill_step(cfg, rt)
        if cfg.is_enc_dec:
            enc_sh = NamedSharding(mesh, P(dp if b > 1 else None, None, None))
            out_sh = (_logits_sh(rt, b), enc_sh, caches_sh, pos_sh)
            lowered = jax.jit(step, in_shardings=(p_sh, data_sh["frames"],
                                                  data_sh["tokens"]),
                              out_shardings=out_sh).lower(
                params_abs, data["frames"], data["tokens"])
        elif cfg.frontend == "vision":
            out_sh = (_logits_sh(rt, b), caches_sh, pos_sh)
            lowered = jax.jit(step, in_shardings=(p_sh, data_sh["tokens"],
                                                  data_sh["embeds"]),
                              out_shardings=out_sh).lower(
                params_abs, data["tokens"], data["embeds"])
        else:
            out_sh = (_logits_sh(rt, b), caches_sh, pos_sh)
            lowered = jax.jit(step, in_shardings=(p_sh, data_sh["tokens"]),
                              out_shardings=out_sh).lower(
                params_abs, data["tokens"])
    else:  # decode
        data = _decode_inputs(cfg, b, s)
        data_sh = S.data_shardings(rt, data, kind=kind)
        caches_abs = S.cache_specs(cfg, b, s)
        caches_sh = S.cache_shardings(rt, cfg, caches_abs, batch=b)
        step = build_decode_step(cfg, rt)
        out_sh = (_logits_sh(rt, b), caches_sh, data_sh["cache_pos"])
        if cfg.is_enc_dec:
            jitted = jax.jit(step,
                             in_shardings=(p_sh, data_sh["token"],
                                           data_sh["enc_out"], caches_sh,
                                           data_sh["cache_pos"]),
                             out_shardings=out_sh, donate_argnums=(3,))
            lowered = jitted.lower(params_abs, data["token"], data["enc_out"],
                                   caches_abs, data["cache_pos"])
        else:
            jitted = jax.jit(step,
                             in_shardings=(p_sh, data_sh["token"], caches_sh,
                                           data_sh["cache_pos"]),
                             out_shardings=out_sh, donate_argnums=(2,))
            lowered = jitted.lower(params_abs, data["token"], caches_abs,
                                   data["cache_pos"])

    meta = dict(arch=arch, shape=shape, kind=kind, global_batch=b, seq_len=s,
                n_devices=int(mesh.devices.size),
                mesh_shape=list(mesh.devices.shape),
                mesh_axes=list(mesh.axis_names),
                params_total=cfg.param_count(),
                params_active=cfg.active_param_count())
    return lowered, meta


def _train_inputs(cfg, b, s):
    d = cfg.d_model
    if cfg.is_enc_dec:
        return {"frames": jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((b, S.dec_len(cfg, s)), jnp.int32)}
    if cfg.frontend == "vision":
        p = cfg.frontend_len
        return {"tokens": jax.ShapeDtypeStruct((b, s - p), jnp.int32),
                "embeds": jax.ShapeDtypeStruct((b, p, d), jnp.bfloat16)}
    return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}


_prefill_inputs = _train_inputs


def _decode_inputs(cfg, b, s):
    out = {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32),
           "cache_pos": jax.ShapeDtypeStruct((b,), jnp.int32)}
    if cfg.is_enc_dec:
        out["enc_out"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    return out


def _prefill_cache_len(cfg, s):
    return S.dec_len(cfg, s) if cfg.is_enc_dec else s


def model_flops(meta) -> float:
    """Analytic useful-FLOPs: 6*N_active*tokens (train) / 2*N_active*tokens
    (inference). The spec's 6-N-D convention is the training number; we report
    the matching convention per step kind."""
    n = meta["params_active"]
    if meta["kind"] == "train":
        return 6.0 * n * meta["global_batch"] * meta["seq_len"]
    if meta["kind"] == "prefill":
        return 2.0 * n * meta["global_batch"] * meta["seq_len"]
    return 2.0 * n * meta["global_batch"]          # decode: one token per seq


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str, *,
             reduced: bool = False, save_hlo: bool = False,
             overrides: dict | None = None, tag: str = "") -> dict:
    cfg = reduced_config(arch) if reduced else get_config(arch)
    ok, note = shape_applicable(cfg, shape)
    cell_id = f"{arch}__{shape}__{mesh_kind}" + (f"__{tag}" if tag else "")
    if not ok:
        rec = dict(arch=arch, shape=shape, mesh=mesh_kind, skipped=True,
                   note=note)
        _write(out_dir, cell_id, rec)
        print(f"[dryrun] SKIP {cell_id}: {note}")
        return rec

    if reduced:
        mesh = make_test_mesh(2, 2, multi_pod=(mesh_kind == "multi"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    lowered, meta = build_lowering(arch, shape, mesh, reduced=reduced,
                                   overrides=overrides)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_rec[attr] = int(v)
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost_rec = {k: float(v) for k, v in (cost or {}).items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "transcendentals", "bytes accessed")
                    or k.startswith("bytes accessed"))}
    hlo = compiled.as_text()
    t3 = time.time()
    hlo_rec = analyze_hlo(hlo)   # loop-corrected FLOPs/bytes/collectives

    rec = dict(meta, mesh=mesh_kind, skipped=False,
               lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
               analyze_s=round(time.time() - t3, 2),
               memory=mem_rec, cost_analysis_raw=cost_rec,
               hlo_flops=hlo_rec["dot_flops"],
               hlo_mem_bytes=hlo_rec["mem_bytes_est"],
               collectives=hlo_rec["collectives"],
               model_flops=model_flops(meta), hlo_bytes_text=len(hlo))
    _write(out_dir, cell_id, rec)
    print(f"[dryrun] OK {cell_id}: compile={rec['compile_s']}s "
          f"hlo_flops={rec['hlo_flops']:.3e} "
          f"model_flops={rec['model_flops']:.3e} "
          f"coll_wire_GB={hlo_rec['collectives']['bytes_wire']/1e9:.2f}")
    if save_hlo:
        with open(os.path.join(out_dir, cell_id + ".hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def _write(out_dir: str, cell_id: str, rec: dict):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    from repro.distributed.sharding import force_host_device_count

    # Before first backend use: jax locks the device count on init. A no-op
    # when XLA_FLAGS already names a count (the CI smoke test uses 8).
    force_host_device_count(512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[None, *SHAPES])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny configs + 8-device test mesh (CI smoke)")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override field=value (int/float/bool), e.g. "
                         "--set mamba_scan_unroll=8 (perf-iteration variants)")
    ap.add_argument("--tag", default="",
                    help="artifact suffix for variant runs")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = int(v)
        except ValueError:
            try:
                overrides[k] = float(v)
            except ValueError:
                if v in ("true", "True", "false", "False"):
                    overrides[k] = v in ("true", "True")
                else:
                    overrides[k] = v          # plain string (e.g. int8)

    from repro.configs import ARCH_IDS
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                cell = f"{arch}__{shape}__{mk}"
                path = os.path.join(args.out, cell + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] cached {cell}")
                    continue
                try:
                    run_cell(arch, shape, mk, args.out, reduced=args.reduced,
                             save_hlo=args.save_hlo, overrides=overrides,
                             tag=args.tag)
                except Exception as e:  # record and continue the sweep
                    failures.append((cell, repr(e)))
                    _write(args.out, cell, dict(
                        arch=arch, shape=shape, mesh=mk, skipped=False,
                        error=repr(e), trace=traceback.format_exc()[-4000:]))
                    print(f"[dryrun] FAIL {cell}: {e}")
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for c, e in failures:
            print("  ", c, e)
        raise SystemExit(1)
    print("[dryrun] all cells OK")


if __name__ == "__main__":
    main()
