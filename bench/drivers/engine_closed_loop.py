"""A closed loop of clients over ``ContinuousBatchEngine``: each client
holds one request in flight and sends its next when the last finishes.
Each timed call is one engine tick (admissions, then one batched decode
over every slot).  The clients' first requests are admitted in set-up,
and one tick there compiles the decode step.

Traffic keys: ``slots``, ``clients``, ``prompt_len`` and ``gen_len``
(``{"values": [...], "weights": [...]}``, drawn per request from the
seed), and ``check_ticks``: the window's last ticks, whose rows hold the
longest contexts, that the reference recomputes.
"""
from __future__ import annotations

import collections

import jax
import numpy as np

from bench import sites as S


def _draw(ctx, spec):
    v = np.asarray(spec["values"])
    p = np.asarray(spec["weights"], np.float64)
    return int(ctx.rng.choice(v, p=p / p.sum()))


def _submit(ctx, st):
    P = _draw(ctx, ctx.traffic["prompt_len"])
    G = _draw(ctx, ctx.traffic["gen_len"])
    prompt = ctx.rng.integers(0, ctx.model["vocab_size"], P, dtype=np.int32)
    return st["eng"].submit(prompt, max_new=G)


def setup(ctx):
    from repro.launch.batching import ContinuousBatchEngine
    t = ctx.traffic
    Pm, Gm = max(t["prompt_len"]["values"]), max(t["gen_len"]["values"])
    with ctx.phase("init"):
        ctx.make_executor()
        sess = ctx.make_session(1, Pm, Gm)
    eng = ContinuousBatchEngine(sess, max_slots=t["slots"], max_len=Pm + Gm)
    ctx.site_shapes = S.site_shapes(sess)
    st = {"eng": eng, "admitted": set(),
          "kept": collections.deque(maxlen=t["check_ticks"])}

    # hold the device logits of each decode call (no copy, no sync)
    st["decode"] = eng._decode

    def held_decode(*args):
        logits, cache = st["decode"](*args)
        st["logits"] = logits
        return logits, cache

    eng._decode = held_decode
    with ctx.phase("plans_states"):
        eng.refresh_states()
        jax.block_until_ready(eng._states)
    with ctx.phase("compile_warmup"):
        for _ in range(t["clients"]):
            _submit(ctx, st)
        eng.try_admit()
        st["admitted"].update(eng.requests)
        _tick(ctx, st, timed=False)
    return st


def _tick(ctx, st, timed=True):
    eng = st["eng"]
    eng.try_admit()
    admitted = []
    for rid, req in eng.requests.items():
        if rid not in st["admitted"] and req.status != "queued":
            st["admitted"].add(rid)
            admitted.append(req.prompt.size)
    rows = [(i, rid, eng.requests[rid].next_pos if rid is not None else 0)
            for i, rid in enumerate(eng.slots)]
    finished = eng.step()
    live = [(i, rid, p) for i, rid, p in rows if rid is not None]
    if timed:                  # the last ticks' logits, held on device
        st["kept"].append((rows, st["logits"]))
    for _ in finished:
        _submit(ctx, st)
    launches = S.launches(ctx.site_shapes, admitted + [eng.max_slots])
    ctx_sum = sum(p + 1 for _, _, p in live) \
        + sum(a * (a + 1) // 2 for a in admitted)
    return {"rows": len(live) + sum(admitted), "launches": launches,
            "ctx_sum": ctx_sum}


def step(ctx, st):
    return _tick(ctx, st)


def check(ctx, st):
    """The checked ticks: each row's fed sequence and position, and the
    served logits of the live rows, on the host."""
    eng = st["eng"]
    out = []
    for rows, logits in st["kept"]:
        seqs, pos, live = [], [], []
        for slot, rid, p in rows:
            if rid is None:                           # an idle slot decodes
                seqs.append(np.zeros(1, np.int32))    # token 0 at 0
                pos.append(0)
                continue
            req = eng.requests[rid]
            seqs.append(np.concatenate([req.prompt,
                                        np.asarray(req.out, np.int32)]))
            pos.append(p)
            live.append((slot, req.out[p - req.prompt.size + 1]
                         if p - req.prompt.size + 1 < len(req.out) else -1))
        out.append((seqs, pos, live, np.asarray(logits, np.float32)))
    return {"ticks": out}


def compare(ctx, data, controls=()):
    """Every checked row against the reference; for each control
    (``sites.CONTROLS``), the reference at its lower precision put in the
    program's place (its logits at the same positions, its first token in
    place of the served one) and compared the same way."""
    ref = S.reference(ctx)
    low = {c: S.reference(ctx, c) for c in controls}
    V = ctx.model["vocab_size"]
    rows = {k: ([], []) for k in ("program",) + tuple(controls)}
    for seqs, pos, live, logits in data["ticks"]:
        r = ref.tick(seqs, pos)
        idx = [s for s, _ in live]
        got = {"program": (logits[idx, :V], [t for _, t in live])}
        for c, dec in low.items():
            lo = dec.tick(seqs, pos)[idx]
            got[c] = (lo, lo.argmax(-1))
        for k, (served, toks) in got.items():
            rows[k][0].extend(S.row_errs(served, r[idx]))
            rows[k][1].extend(S.token_gaps(toks, r[idx]))
    return {k: S.summary(*v) for k, v in rows.items()}
