"""Serving: batched prefill + decode with a KV cache, as a CLI and as a
programmatic ``ServeSession``.

CLI (the original launcher, now a thin wrapper over ``ServeSession``):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --batch 4 --prompt-len 32 --gen 16

``ServeSession`` is the task-level API the ROADMAP's "task-level
robustness" item asks for: it builds the model once, discovers every
analog dense() call site, and threads one ``DeploymentState`` per site
through its compiled prefill/decode steps as TRACED arguments.  A
``ScenarioSweep``-style loop can therefore swap the whole fleet's device
state between ``generate()`` calls -- corners, ages, remaps, retrained
params, recalibrations -- and the serving steps never recompile
(``decode_traces`` stays 1; asserted by ``benchmarks/bench_task.py``,
which turns this into accuracy-vs-sigma / accuracy-vs-age curves on
actual token prediction).

State threading covers scanned and unrolled layers alike.  Call sites
in Python-unrolled layers are keyed ``"<tag>#<ordinal>"`` (model tags
repeat across layers; trace order is deterministic); call sites inside
the model's ``lax.scan`` over layer periods are keyed
``"<group>.<period>:<tag>#<ordinal>"`` (``group`` is ``dec``/``enc``)
and the scan body picks its period's states by the period index it
carries (never a stacked copy of every period) -- so full-depth
scanned models get the
same zero-recompile corner/age/remap sweeps as unrolled ones (the
legacy bake-in-at-trace-time fallback is gone).  A deployment is
serializable either way: ``--state-save`` writes the served per-site
states + spec to npz (``core.deployment.save_deployment``) and
``--state-load`` restores them verbatim in another process -- same
fleet, same age, same remap, same read-noise draw, bit-identical
tokens.

Batched multi-request serving (continuous batching, paged KV slots,
Poisson-load benchmarks) lives one level up in
``repro.launch.batching`` (docs/serving.md).
"""
import argparse
import contextlib
import itertools
import json
import os
import time
from typing import Dict, Optional

from repro.obs import OBS

# per-process serving call-site ordinal: telemetry series from two
# sessions of the same arch stay distinguishable
_SESSION_IDS = itertools.count()


class ServeSession:
    """A reusable serving session over one model + one analog executor.

    Builds params, prompt and compiled steps once; ``generate()`` runs
    prefill + greedy/temperature decode and returns tokens, per-step
    logits and timings.  With an ``executor``, the analog layers' device
    states enter the compiled steps as traced arguments (see module
    docstring); ``generate()`` re-materializes them from the executor's
    ACTIVE deployment each call, so the usage for a sweep is::

        sess = ServeSession("gemma3-1b", executor=ex, ...)
        for sigma in sigmas:
            ex.deploy(scenario=Scenario(name="s", prog_sigma=sigma), key=k)
            sess.calibrate(n=16)
            out = sess.generate()          # zero recompiles across sigmas

    With ``executor=None`` the session serves the plain digital model
    (the reference for task-level accuracy).  ``params`` serves the given
    weights (the serving layout of ``models.model.model_schema``, bf16)
    in place of the session's own draw, which is then not made.
    """

    def __init__(self, arch: str, *, reduced: bool = True,
                 reduced_layers: Optional[int] = None, batch: int = 4,
                 prompt_len: int = 32, gen: int = 16,
                 temperature: float = 0.0, seed: int = 0, executor=None,
                 params=None):
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config, reduced as reduce_cfg
        from repro.configs.base import ParallelConfig
        from repro.runtime import steps as S
        self._jax, self._jnp = jax, jnp

        cfg = get_config(arch)
        if reduced:
            cfg = reduce_cfg(cfg, layers=reduced_layers)
        self.cfg = cfg
        self.B, self.P, self.G = batch, prompt_len, gen
        self.temperature = temperature
        self.seed = seed
        self.ex = executor
        pcfg = ParallelConfig(attn_block_kv=min(1024, prompt_len),
                              xent_chunk=128,
                              scan_chunk=min(256, prompt_len))

        # explicit key threading: every stochastic path (param init,
        # prompt, sampling) gets its own derived key
        root = jax.random.PRNGKey(seed)
        k_init, k_prompt, k_img, k_enc, self._key = jax.random.split(root, 5)
        # parameters only (the key init_train_state gives them, so the
        # values match a trainer's): serving needs no optimizer moments.
        # Each leaf is drawn in f32 and rounded to bf16 as it is made, so
        # no f32 copy of the whole model is ever held.
        if params is None:
            from repro.models.common import init_params
            from repro.models.model import model_schema
            with OBS.span("session_init"):
                params = init_params(k_init, model_schema(cfg),
                                     dtype=jnp.bfloat16)
                params = jax.tree.map(lambda v: v.astype(jnp.bfloat16),
                                      params)
        self.params = params
        prompt = jax.random.randint(k_prompt, (batch, prompt_len), 0,
                                    cfg.vocab_size)
        self.batch = {"tokens": prompt}
        if cfg.frontend == "vision":
            self.batch["image_embeds"] = jax.random.normal(
                k_img, (batch, cfg.frontend_tokens, cfg.d_model),
                jnp.bfloat16)
        if cfg.encoder_layers:
            self.batch["enc_frames"] = jax.random.normal(
                k_enc, (batch, prompt_len, cfg.d_model), jnp.bfloat16)

        # telemetry identity of this serving call site (docs/observability
        # .md): every session-level metric series carries site=<this>
        self.site = f"{arch}#{next(_SESSION_IDS)}"
        self._prefill_step = S.make_prefill_step(cfg, pcfg)
        self._decode_step = S.make_decode_step(cfg, pcfg)
        # per-site state threading: unrolled sites as plain traced args,
        # scanned sites picked per period inside the scan (module docstring)
        self.threading = executor is not None
        self._sites: Optional[Dict[str, object]] = None
        self._steps_built = False
        self._last_states: Optional[dict] = None
        self.prefill_traces = 0
        self.decode_traces = 0

    # ------------------------------------------------------------------ #
    # Analog call-site discovery + device-state materialization
    # ------------------------------------------------------------------ #
    def sites(self) -> Dict[str, object]:
        """``site_key -> weight`` for every analog dense() call site,
        discovered once with a zero-FLOP ``jax.eval_shape`` pass (the
        model's weights are concrete; only activations are abstract)."""
        if self.ex is None:
            return {}
        if self._sites is None:
            from repro.core.analog import _StateBinding
            from repro.models.common import use_dense_hook, use_scan_states
            rec: Dict[str, object] = {}
            binding = _StateBinding(record=rec)
            with OBS.span("session_sites"), use_dense_hook(self.ex.hook), \
                    use_scan_states(binding), self.ex.bound_states(binding):
                self._jax.eval_shape(
                    lambda b: self._prefill_step(self.params, b), self.batch)
            self._sites = rec
        return self._sites

    def states(self) -> Dict[str, object]:
        """One ready-to-serve ``DeploymentState`` per call site,
        materialized from the executor's ACTIVE deployment."""
        sites = self.sites()
        with OBS.span("session_states"):
            sts = {sk: self.ex.state_for(sk, w) for sk, w in sites.items()}
        if OBS.enabled:
            for sk in sts:
                OBS.counter("serve_state_swaps_total",
                            "DeploymentStates materialized and threaded "
                            "into the compiled steps, per analog call site",
                            site=self.site, call_site=sk).inc()
        return sts

    def calibrate(self, key=None, n: int = 16,
                  warm_start: bool = False) -> None:
        """Fit every call site's volts->logical affine against digital
        under the executor's active deployment (noise-aware; reuses each
        site's ONE compiled forward across sweep points)."""
        jax = self._jax
        if key is None:
            key = jax.random.PRNGKey(self.seed + 1)
        for i, (sk, w) in enumerate(sorted(self.sites().items())):
            self.ex.calibrate(jax.random.fold_in(key, i), w, sk, n=n,
                              warm_start=warm_start)

    def save_deployment(self, path: str) -> str:
        """Serialize the last-served (or current) per-site states + the
        deployment spec to npz (``serve --state-save``)."""
        from repro.core.deployment import save_deployment
        states = self._last_states if self._last_states else self.states()
        return save_deployment(path, states, self.ex.deployment)

    # ------------------------------------------------------------------ #
    # Compiled serving steps (device states as traced arguments)
    # ------------------------------------------------------------------ #
    def _bound(self, states):
        if self.ex is None:
            return contextlib.nullcontext()
        from repro.core.analog import _StateBinding
        from repro.models.common import use_dense_hook, use_scan_states
        binding = _StateBinding(states=states)
        stack = contextlib.ExitStack()
        stack.enter_context(use_dense_hook(self.ex.hook))
        stack.enter_context(use_scan_states(binding))
        stack.enter_context(self.ex.bound_states(binding))
        return stack

    def _build_steps(self):
        jax = self._jax

        # params ride as arguments: closed over, they would be embedded
        # in every executable as constants (a full-width model twice over)
        def run_prefill(params, b, states, all_positions=False, taps=False):
            self.prefill_traces += 1           # trace-time side effect
            if OBS.enabled:
                OBS.counter("serve_traces_total",
                            "jit traces of the serving steps (a healthy "
                            "sweep holds this at 1 per step)",
                            site=self.site, step="prefill").inc()
            with self._bound(states):
                return self._prefill_step(params, b, all_positions, taps)

        def run_decode(params, tok, cache, pos, states):
            self.decode_traces += 1
            if OBS.enabled:
                OBS.counter("serve_traces_total",
                            "jit traces of the serving steps (a healthy "
                            "sweep holds this at 1 per step)",
                            site=self.site, step="decode").inc()
            with self._bound(states):
                return self._decode_step(params, tok, cache, pos)

        self._prefill = jax.jit(run_prefill,
                                static_argnames=("all_positions", "taps"))
        self._decode = jax.jit(run_decode, donate_argnums=(2,))
        self._steps_built = True

    def prefill(self, tokens=None, states: Optional[dict] = None, *,
                all_positions: bool = False, taps: bool = False) -> dict:
        """One prefill of a (B, S) token batch (default: the session's
        prompt): the step ``generate`` starts with.

        ``states`` as in ``generate``; ``all_positions`` and ``taps`` as in
        ``models.model.prefill`` (each pair of them is its own compiled
        step).  Returns device arrays, unsynced: ``{"logits", "cache"}``,
        and ``"taps"`` when asked."""
        if not self._steps_built:
            self._build_steps()
        if states is None:
            self._last_states = None
            states = self.states() if self.threading else {}
        self._last_states = states
        b = self.batch if tokens is None else {"tokens": tokens}
        # only what differs from generate's call, so the two share a trace
        kw = {k: True for k, on in (("all_positions", all_positions),
                                    ("taps", taps)) if on}
        out = self._prefill(self.params, b, states, **kw)
        return dict(zip(("logits", "cache", "taps"), out))

    def generate(self, states: Optional[dict] = None) -> dict:
        """One prefill + greedy/temperature decode pass.

        ``states`` overrides the per-site device states (e.g. loaded from
        ``--state-load``); by default they re-materialize from the
        executor's active deployment.  Returns ``{"tokens": (B, G) int
        array, "logits": (G, B, V) float array, "prefill_s", "decode_s"}``.
        Repeated calls with swapped deployments reuse the same compiled
        steps (``prefill_traces`` / ``decode_traces`` stay 1)."""
        jax, jnp = self._jax, self._jnp
        import numpy as np
        from repro.models import model as M
        if not self._steps_built:
            self._build_steps()
        if states is None:
            # release the last corner's states first: at full width one
            # set of states is GBs, and two need not coexist
            self._last_states = None
            states = self.states() if self.threading else {}
        self._last_states = states
        B, P, G = self.B, self.P, self.G
        total = P + G

        t0 = time.time()
        logits, pcache = self._prefill(self.params, self.batch, states)
        logits.block_until_ready()
        t_prefill = time.time() - t0
        if OBS.enabled:
            OBS.histogram("serve_prefill_seconds",
                          "full prefill wall clock (synchronized) per "
                          "serving call site", site=self.site,
                          arch=self.cfg.name).observe(t_prefill)

        # build a generation cache sized for P+G, splice the prefill cache
        cs = M.model_cache_schema(
            self.cfg, B, total,
            cross_len=(P if self.cfg.encoder_layers else 0))
        cache = M.zeros_cache(cs)

        def splice(z, c):
            c = c.astype(z.dtype)
            if z.shape == c.shape:
                return c
            if z.ndim == c.ndim and z.shape[2:] == c.shape[2:] and \
                    z.shape[0] == c.shape[0]:
                return jax.lax.dynamic_update_slice(
                    z, c, (0,) * c.ndim)       # prompt occupies [0, P)
            if z.ndim == c.ndim and z.shape[3:] == c.shape[3:] and \
                    z.shape[:2] == c.shape[:2]:
                return jax.lax.dynamic_update_slice(z, c, (0,) * c.ndim)
            return z
        cache = jax.tree.map(splice, cache, pcache)

        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        # keep logits on device inside the timed loop (a host transfer
        # per step would serialize the dispatch pipeline); convert once
        # at the end
        out_tokens, out_logits = [tok], [logits]
        t0 = time.time()
        for i in range(G - 1):
            logits, cache = self._decode(self.params, tok, cache,
                                         jnp.asarray(P + i, jnp.int32),
                                         states)
            if self.temperature > 0:
                self._key, sub = jax.random.split(self._key)
                tok = jax.random.categorical(
                    sub, logits / self.temperature,
                    axis=-1)[:, None].astype(jnp.int32)
            else:
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            out_tokens.append(tok)
            out_logits.append(logits)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0
        if OBS.enabled:
            OBS.histogram("serve_decode_seconds",
                          "full decode-loop wall clock (synchronized) per "
                          "serving call site", site=self.site,
                          arch=self.cfg.name).observe(t_decode)
            OBS.counter("serve_tokens_total",
                        "tokens served (prompt + generated)",
                        site=self.site, arch=self.cfg.name).inc(
                            B * (P + G))
        return {"tokens": np.asarray(jnp.concatenate(out_tokens, axis=1)),
                "logits": np.stack([np.asarray(l, np.float32)
                                    for l in out_logits]),
                "prefill_s": t_prefill, "decode_s": t_decode}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="reduced layer count override (below the arch's "
                         "pattern length the layers unroll; state "
                         "threading and --state-save/--state-load work "
                         "for scanned and unrolled layers alike)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="serve the analog plane tensor-parallel on a "
                         "(data, model) mesh of this shape: DeploymentState "
                         "leaves shard over the tile lattice and the bitline "
                         "reduction runs as one psum (docs/parallel.md); "
                         "requires a non-digital --analog-backend and "
                         "DP*TP available devices (combine with --devices "
                         "to force host devices)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed; init/prompt/sampling/device-noise each "
                         "get their own derived key, so noisy-scenario "
                         "inference is reproducible")
    ap.add_argument("--analog-backend", default="digital",
                    choices=["digital", "analytic", "circuit", "emulator"],
                    help="route MLP projections through the analog fast path")
    ap.add_argument("--emulator-params", default=None,
                    help="npz with trained Conv4Xbar params (benchmarks cache "
                         "format); required for --analog-backend=emulator")
    ap.add_argument("--scenario", default=None,
                    help="device non-ideality scenario name from the "
                         "repro.nonideal registry (e.g. prog_mild, stressed); "
                         "requires a non-digital --analog-backend")
    ap.add_argument("--age", type=float, default=None,
                    help="seconds since the fleet was programmed: ages the "
                         "scenario's drift_t (serve an aged fleet; see "
                         "docs/lifetime.md)")
    ap.add_argument("--fault-remap", action="store_true",
                    help="stuck-fault-aware column remapping: permute output "
                         "columns so large weights avoid the scenario's "
                         "stuck-off cells (requires --scenario)")
    ap.add_argument("--conditioned-emulator", action="store_true",
                    help="require --emulator-params to hold a scenario-"
                         "conditioned Conv4Xbar (peripheral width > 2): one "
                         "net serves every --scenario/--age corner with zero "
                         "retraining (docs/emulator.md)")
    ap.add_argument("--state-save", default=None, metavar="NPZ",
                    help="after serving, write the deployment (per-site "
                         "DeploymentStates + spec) to this npz so another "
                         "process can reproduce it with --state-load")
    ap.add_argument("--state-load", default=None, metavar="NPZ",
                    help="serve a deployment saved with --state-save: the "
                         "per-site device states (fleet draw, age, remap, "
                         "read keys, calibration) are restored verbatim")
    ap.add_argument("--telemetry", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="enable the metrics registry for this run and dump "
                         "the JSON snapshot on exit -- to PATH, or to stdout "
                         "when the flag is given bare (docs/observability.md)")
    args = ap.parse_args()
    if args.telemetry is not None:
        OBS.enable()
    if args.scenario and args.analog_backend == "digital":
        ap.error("--scenario requires a non-digital --analog-backend")
    if (args.fault_remap or args.age is not None) and not args.scenario:
        ap.error("--fault-remap / --age require --scenario")
    if args.conditioned_emulator and args.analog_backend != "emulator":
        ap.error("--conditioned-emulator requires --analog-backend=emulator")
    if (args.state_save or args.state_load) \
            and args.analog_backend == "digital":
        ap.error("--state-save/--state-load require a non-digital "
                 "--analog-backend")
    if args.mesh is not None and args.analog_backend == "digital":
        ap.error("--mesh shards the analog plane and requires a "
                 "non-digital --analog-backend")

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    # optional: serve the MLP projections on emulated analog hardware (the
    # SEMULATOR serving path; uses the cached-conductance-plan fast path)
    ex = None
    loaded_states = None
    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_serve_mesh
        try:
            dp, tp = (int(v) for v in args.mesh.split(","))
        except ValueError:
            ap.error(f"--mesh expects DP,TP (got {args.mesh!r})")
        mesh = make_serve_mesh(dp, tp)
        print(f"serving mesh: (data, model) = ({dp}, {tp})")
    if args.analog_backend != "digital":
        import numpy as np
        from repro.configs.base import AnalogConfig
        from repro.configs.rram_ps32 import CASE_A
        from repro.core.analog import AnalogExecutor
        eparams = None
        if args.analog_backend == "emulator":
            assert args.emulator_params, \
                "--analog-backend=emulator needs --emulator-params <npz>"
            data = np.load(args.emulator_params, allow_pickle=True)
            eparams = {k: jnp.asarray(v) for k, v in data.items()
                       if not k.startswith("__")}
        ex = AnalogExecutor(
            acfg=AnalogConfig(enabled=True, backend=args.analog_backend,
                              layers=("mlp",)),
            geom=CASE_A, emulator_params=eparams, mesh=mesh)
        if args.conditioned_emulator:
            from repro.nonideal import (N_SCENARIO_FEATURES,
                                        SCENARIO_FEATURE_NAMES)
            assert ex.emulator_conditioned, \
                "--conditioned-emulator: params are not scenario-" \
                "conditioned (peripheral width must be 2 + " \
                f"{N_SCENARIO_FEATURES}; train with " \
                "nonideal.data.train_conditioned_emulator)"
            print(f"conditioned emulator: {N_SCENARIO_FEATURES} scenario "
                  f"features ({', '.join(SCENARIO_FEATURE_NAMES[:4])}, ...)")
        if args.state_load:
            from repro.core.deployment import load_deployment
            # executor=ex: loaded host arrays land straight on the serving
            # mesh (re-shard-on-load; the npz records values, not placements)
            loaded_states, dep = load_deployment(args.state_load, executor=ex)
            ex.deploy(scenario=dep.scenario, key=dep.key, remap=dep.remap,
                      states=dep.states)
            print(f"deployment restored: {len(loaded_states)} call sites "
                  f"from {args.state_load}")
        elif args.scenario:
            from repro.nonideal import get_scenario
            k_dev = jax.random.fold_in(jax.random.PRNGKey(args.seed), 0xDEF)
            ex.deploy(scenario=get_scenario(args.scenario), age=args.age,
                      remap=args.fault_remap, key=k_dev)
            print(f"analog scenario: {ex.scenario}")

    sess = ServeSession(args.arch, reduced=args.reduced,
                        reduced_layers=args.layers, batch=args.batch,
                        prompt_len=args.prompt_len, gen=args.gen,
                        temperature=args.temperature, seed=args.seed,
                        executor=ex)
    from repro.obs import RecompileSentinel
    with RecompileSentinel(session=sess, executor=ex, strict=False,
                           label="serve") as sent:
        out = sess.generate(states=loaded_states)

    B, P, G = args.batch, args.prompt_len, args.gen
    print(f"prefill {B}x{P}: {out['prefill_s']*1e3:.1f} ms "
          f"({B*P/out['prefill_s']:.0f} tok/s)")
    print(f"decode  {G-1} steps: {out['decode_s']*1e3:.1f} ms "
          f"({B*(G-1)/max(out['decode_s'],1e-9):.0f} tok/s)")
    print("sample tokens[0]:", out["tokens"][0, :12].tolist())
    if args.state_save:
        path = sess.save_deployment(args.state_save)
        print(f"deployment saved: {len(sess._last_states)} call sites "
              f"-> {path}")
    if args.telemetry is not None:
        if not sent.ok:
            print(f"WARNING recompile sentinel tripped: {sent.violations}")
        from repro.obs import snapshot as obs_snapshot
        if args.telemetry == "-":
            print(json.dumps(obs_snapshot(), indent=2, sort_keys=True))
        else:
            from repro.obs import write_snapshot
            write_snapshot(args.telemetry)
            print(f"telemetry snapshot -> {args.telemetry}")


if __name__ == "__main__":
    main()
