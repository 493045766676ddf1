"""A cell's configuration at CPU-test widths (the published file is
untouched): hidden 64, FFN 128, 4 heads of 16, 2 KV heads, vocab 256."""
import copy

from bench import harness as H


def conf(workload: str) -> dict:
    cell = {w["name"]: w for w in H.benchmark()["workloads"]}[workload]
    c = copy.deepcopy(H.load_json("configs", cell["config"] + ".json"))
    c["config"].update(hidden_size=64, intermediate_size=128,
                       num_attention_heads=4, num_key_value_heads=2,
                       vocab_size=256)
    c["assumed"]["head_dim"] = 16
    return c


def run(workload: str, seed: int, seconds: float = 0.5, **kw):
    import time
    return H.run(workload, seed, seconds, False, t_start=time.perf_counter(),
                 require_tpu=False, conf_override=conf(workload),
                 log=lambda *a, **k: None, **kw)


def ctx_for(workload: str, seed: int):
    """The context ``harness.run`` builds, for driving a driver by
    hand."""
    import numpy as np
    cell, _, traffic, _ = H.cell_spec(workload)
    c = conf(workload)
    ctx = H.Ctx(cell=cell, conf=c, traffic=traffic, seed=seed,
                spans=H.Spans(), rng=np.random.default_rng(seed),
                device_kind="cpu")
    ctx.model = H.model_of(c)
    ctx.arch = H.arch_config(c)
    return ctx, H.load_module("drivers", traffic["kind"])
