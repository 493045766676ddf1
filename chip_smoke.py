#!/usr/bin/env python3
"""Bring-up smoke run of the SEMULATOR serving path on one TPU chip.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4

One process, on the chip, through the entry points a user calls:

  (a) train a Conv4Xbar emulator for a few steps on circuit-solver data
      made from --seed, at the CI smoke size;
  (b) serve digital gemma3-1b at its published widths and depth through
      ``ServeSession.generate`` (batch 2, prompt 16, gen 4), then four
      requests through ``ContinuousBatchEngine.run``;
  (c) serve gemma3-1b with the MLP projections on the emulator (params
      from (a)) at the ideal corner, then swap to the ``stressed`` corner
      under a ``RecompileSentinel`` -- no step may recompile;
  (d) check the unified Pallas kernel against the paper-faithful
      ``conv4xbar.apply`` (at ``precision="highest"``) on a slice of the
      blocks of one real-width MLP site.

``--chips 4`` runs only the tensor-parallel path: the emulator serve on
a (data, model) = (1, 4) mesh beside the unsharded executor on the same
host, compared under the col-scheme contract of docs/parallel.md.

Any failure raises, so the exit code is non-zero.  With no TPU it exits
non-zero before any phase.  The numbers printed are a bring-up record,
not a benchmark.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

ARCH = "gemma3-1b"
BATCH, PROMPT, GEN = 2, 16, 4
# kernel vs conv4xbar.apply at precision="highest": two evaluations of the
# same net that associate their f32 sums differently, with the TPU's
# exp and the kernel's exp-based CELU (Mosaic has no expm1) on one side
KERNEL_TOL = dict(rtol=1e-4, atol=1e-4)
KERNEL_SLICE_NO = 256          # output groups of the site checked in (d)

_COMPILE_S = [0.0]


def _on_compile(event, duration, **_):
    if event.endswith("backend_compile_duration"):
        _COMPILE_S[0] += duration


def log(msg):
    print(msg, flush=True)


def device_bytes(stat="peak_bytes_in_use"):
    import jax
    return jax.devices()[0].memory_stats()[stat]


def check(ok, what):
    """Fail the run (a plain ``assert`` would vanish under ``-O``)."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def finite(a):
    import numpy as np
    return bool(np.all(np.isfinite(np.asarray(a, np.float32))))


def emulator_executor(eparams, mesh=None):
    from repro.configs.base import AnalogConfig
    from repro.configs.rram_ps32 import CASE_A
    from repro.core.analog import AnalogExecutor
    return AnalogExecutor(
        acfg=AnalogConfig(enabled=True, backend="emulator", layers=("mlp",)),
        geom=CASE_A, emulator_params=eparams, mesh=mesh)


def session(seed, executor=None, reduced=False):
    from repro.launch.serve import ServeSession
    sess = ServeSession(ARCH, reduced=reduced, batch=BATCH,
                        prompt_len=PROMPT, gen=GEN, seed=seed,
                        executor=executor)
    c = sess.cfg
    log(f"model {c.name}: d_model {c.d_model} d_ff {c.d_ff} vocab "
        f"{c.vocab_size} layers {c.num_layers}"
        + (f" analog sites {len(sess.sites())}" if executor else ""))
    return sess


def timed_generate(sess, label):
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    out = sess.generate()
    wall = time.perf_counter() - t0
    toks = BATCH * (PROMPT + GEN)
    log(f"{label}: {wall:.3f} s wall, {_COMPILE_S[0] - c0:.3f} s compiling, "
        f"prefill {BATCH * PROMPT / out['prefill_s']:.1f} tok/s, decode "
        f"{BATCH * (GEN - 1) / max(out['decode_s'], 1e-9):.1f} tok/s "
        f"({toks} tokens), peak {device_bytes()} B")
    check(finite(out["logits"]), f"{label}: non-finite logits")
    return out


def phase_train(seed, n_train=512, n_test=128):
    """(a) a few steps of the paper's training protocol on the chip."""
    import jax
    from repro.configs.base import AnalogConfig
    from repro.configs.rram_ps32 import CASE_A, EmulatorTrainConfig
    from repro.core.circuit import CircuitParams
    from repro.core.emulator import train_emulator
    # the CI smoke protocol of benchmarks/bench_speed.py
    tcfg = EmulatorTrainConfig(n_train=n_train, n_test=n_test, epochs=2,
                               lr=2e-3, lr_halve_at=(), batch_size=256,
                               seed=seed)
    t0 = time.perf_counter()
    res = train_emulator(jax.random.PRNGKey(seed), CASE_A, AnalogConfig(),
                         CircuitParams(), tcfg)
    log(f"(a) emulator trained: {tcfg.epochs} epochs x "
        f"{n_train // tcfg.batch_size} steps, train mse {res.train_mse:.6g} "
        f"test mse {res.test_mse:.6g}, {time.perf_counter() - t0:.3f} s, "
        f"peak {device_bytes()} B")
    check(finite([res.train_mse, res.test_mse]), "(a) loss not finite")
    return res.params


def phase_digital(seed, reduced=False):
    """(b) digital serving at full width, then the batching engine."""
    from repro.launch.batching import ContinuousBatchEngine
    import numpy as np
    sess = session(seed, reduced=reduced)
    timed_generate(sess, "(b) digital generate, first call")
    timed_generate(sess, "(b) digital generate, warm")
    check(sess.decode_traces == 1, f"decode_traces {sess.decode_traces}")
    eng = ContinuousBatchEngine(sess, max_slots=4)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, sess.cfg.vocab_size, n)
               for n in (PROMPT, PROMPT, PROMPT // 2, PROMPT // 2)]
    t0 = time.perf_counter()
    outs = eng.run(prompts, max_new=GEN)
    log(f"(b) engine: {len(outs)} requests, {time.perf_counter() - t0:.3f} "
        f"s, decode_traces {eng.decode_traces}, peak {device_bytes()} B")
    check([len(o) for o in outs] == [GEN] * len(prompts), f"lengths {outs}")
    check(all(int(o.max()) < sess.cfg.vocab_size for o in outs),
          "token id out of the vocabulary")
    check(eng.decode_traces == 1, f"engine decode_traces {eng.decode_traces}")


def phase_emulator(seed, eparams, reduced=False):
    """(c) emulator-served MLPs, ideal then stressed, no recompile."""
    import jax
    import numpy as np
    from repro.nonideal import get_scenario
    from repro.obs import RecompileSentinel
    ex = emulator_executor(eparams)
    sess = session(seed, executor=ex, reduced=reduced)
    ideal = timed_generate(sess, "(c) emulator generate, ideal, first call")
    with RecompileSentinel(session=sess, executor=ex, max_traces=0,
                           label="chip-smoke-stressed"):
        ex.deploy(scenario=get_scenario("stressed"),
                  key=jax.random.PRNGKey(seed + 1))
        stressed = timed_generate(sess, "(c) emulator generate, stressed")
    log(f"(c) decode_traces {sess.decode_traces} prefill_traces "
        f"{sess.prefill_traces}; stressed - ideal max |logit| "
        f"{float(np.max(np.abs(stressed['logits'] - ideal['logits'])))}")
    check(sess.decode_traces == 1 and sess.prefill_traces == 1,
          "a serving step retraced across the corner swap")
    check(not np.array_equal(stressed["logits"], ideal["logits"]),
          "the stressed corner served the ideal logits")
    return sess, ex


def kernel_check(sess, ex, seed):
    """(d) the kernel on the chip vs the paper-faithful conv stack, and
    the Pallas kernel present in the compiled analog forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import conv4xbar
    from repro.core.crossbar import build_conductance_plan
    from repro.kernels.emulator_block import emulator_block_unified
    site = sorted(k for k in sess.sites() if "mlp.gate" in k)[0]
    w = sess.sites()[site]
    geom, acfg = ex.geom, ex.acfg
    x = jax.random.normal(jax.random.PRNGKey(seed + 2),
                          (BATCH, w.shape[0])) * 0.5

    st = ex.state_for(site, w)
    fwd = jax.jit(lambda xx, s: ex.matmul(xx, w, site, state=s))
    hlo = fwd.lower(x, st).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"(d) site {site} {tuple(w.shape)}: tpu_custom_call x{n_kernels} "
        "in the compiled analog forward")
    check(n_kernels > 0, "analog forward did not compile the Pallas kernel")

    plan = build_conductance_plan(w.astype(jnp.float32), acfg, geom)
    gn = plan.g_norm[:, :KERNEL_SLICE_NO]
    NB, NO, D, H, W = gn.shape
    xs = jnp.max(jnp.abs(x))
    u = plan.tile_v(ex._drive01(jnp.abs(x) / xs), 1.0)
    pos = plan.tile_v((x > 0).astype(jnp.float32), 1.0)
    params = ex.emulator_params
    aux = conv4xbar.blocklast_weights(params, geom)
    y = np.asarray(jax.jit(lambda g, uu, pp: emulator_block_unified(
        aux, g, uu, pp, use_pallas=True))(gn, u, pos))

    shp = (x.shape[0], NB, NO, D, H, W)

    def paper(v):
        xv = jnp.broadcast_to(v[:, :, None, :, :, None], shp)
        xg = jnp.broadcast_to(gn[None], shp)
        blocks = jnp.stack([xv, xg], axis=3).reshape(-1, 2, D, H, W)
        periph = jnp.tile(jnp.asarray([[1.0, 0.0]]), (blocks.shape[0], 1))
        return conv4xbar.apply(params, blocks, periph)

    with jax.default_matmul_precision("highest"):
        ref = np.stack([np.asarray(jax.jit(paper)(v))
                        for v in (u * pos, u * (1.0 - pos))])
    err = np.abs(y - ref)
    log(f"(d) kernel vs conv4xbar.apply on {NB}x{NO} blocks x {x.shape[0]} "
        f"rows: max |err| {float(err.max())}, max |ref| "
        f"{float(np.abs(ref).max())}, tolerance {KERNEL_TOL}")
    np.testing.assert_allclose(y, ref, **KERNEL_TOL)


def phase_mesh(seed, reduced=False):
    """--chips 4: sharded emulator serve at (1, 4) vs the unsharded
    executor, under the col-scheme contract (docs/parallel.md)."""
    import gc
    import jax
    import numpy as np
    from repro.configs.rram_ps32 import CASE_A
    from repro.core import conv4xbar
    from repro.models.common import init_params
    from repro.parallel.sharding import serve_mesh
    eparams = init_params(jax.random.PRNGKey(seed),
                          conv4xbar.conv4xbar_schema(CASE_A, n_periph=2))
    x_key = jax.random.PRNGKey(seed + 3)
    outs, sites_y = {}, {}
    for name, mesh in (("unsharded", None), ("mesh(1,4)", serve_mesh(1, 4))):
        ex = emulator_executor(eparams, mesh=mesh)
        sess = session(seed, executor=ex, reduced=reduced)
        outs[name] = timed_generate(sess, f"{name} emulator generate")
        # the contract proper: one real-width forward per MLP projection
        for tag in ("mlp.gate", "mlp.down"):
            site = sorted(k for k in sess.sites() if tag in k)[0]
            w = sess.sites()[site]
            x = jax.random.normal(x_key, (BATCH * PROMPT, w.shape[0]))
            sites_y[name, tag] = np.asarray(ex.matmul(x, w, site))
            if mesh is not None:
                scheme = ex._scheme_for(*ex.state_for(site, w).gf.shape[:2])
                log(f"{site} {tuple(w.shape)} lattice scheme {scheme}")
                check(scheme == "col", f"lattice scheme {scheme}")
        # one full-width executor at a time fits device 0: JAX's caches
        # keep the last one's buffers alive until they are cleared
        del sess, ex
        jax.clear_caches()
        gc.collect()
        log(f"{name} released: device 0 holds "
            f"{device_bytes('bytes_in_use')} B")
    for tag in ("mlp.gate", "mlp.down"):
        a, b = sites_y["mesh(1,4)", tag], sites_y["unsharded", tag]
        log(f"{tag}: sharded vs unsharded forward max |diff| "
            f"{float(np.max(np.abs(a - b)))}, bitwise {np.array_equal(a, b)}")
        np.testing.assert_array_equal(a, b)
    a, b = outs["mesh(1,4)"], outs["unsharded"]
    log(f"serve logits: sharded vs unsharded max |diff| "
        f"{float(np.max(np.abs(a['logits'] - b['logits'])))}, bitwise "
        f"{np.array_equal(a['logits'], b['logits'])}, tokens equal "
        f"{np.array_equal(a['tokens'], b['tokens'])}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the (1, 4)-mesh sharded serve and "
                         "its unsharded comparison")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
                 "this check never falls back to the CPU")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devs)} devices")
    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    log(f"device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache}")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args.seed)
    else:
        eparams = phase_train(args.seed)
        phase_digital(args.seed)
        sess, ex = phase_emulator(args.seed, eparams)
        kernel_check(sess, ex, args.seed)
    log(f"total {time.perf_counter() - t0:.3f} s, compiling "
        f"{_COMPILE_S[0]:.3f} s, peak {device_bytes()} B")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
