"""Each configuration file against its published widths, and its
``reduced`` keys against what the harness builds."""
import pytest

from bench import harness as H

BENCH = H.benchmark()
CONFS = {c["name"]: c for c in BENCH["configs"]}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "num_local_experts",
          "num_experts_per_tok")


@pytest.mark.parametrize("name", sorted(CONFS))
def test_widths_are_the_published_ones(name):
    from repro.configs import get_config
    conf = H.load_json("configs", name + ".json")
    reg = get_config(conf["arch"])
    c = conf["config"]
    assert c["hidden_size"] == reg.d_model
    assert c["intermediate_size"] == reg.d_ff
    assert c["num_attention_heads"] == reg.num_heads
    assert c["num_key_value_heads"] == reg.num_kv_heads
    assert c["vocab_size"] == reg.vocab_size
    if reg.moe is not None:
        assert c["num_local_experts"] == reg.moe.num_experts
        assert c["num_experts_per_tok"] == reg.moe.top_k
    assert c["num_hidden_layers"] <= reg.num_layers
    assert not set(conf["reduced"]) & set(WIDTHS)


@pytest.mark.parametrize("name", sorted(CONFS))
def test_reduced_lists_every_changed_key(name):
    conf = H.load_json("configs", name + ".json")
    changed = {k for k, v in conf["published"].items()
               if conf["config"].get(k) != v}
    assert changed == set(conf["reduced"]) == set(CONFS[name]["reduced"])
    assert CONFS[name]["file"] == f"bench/configs/{name}.json"
    assert CONFS[name]["source"] == conf["source"]


@pytest.mark.parametrize("name", sorted(CONFS))
def test_harness_builds_the_file(name):
    conf = H.load_json("configs", name + ".json")
    cfg = H.arch_config(conf)
    c = conf["config"]
    assert cfg.name == name
    assert cfg.num_layers == c["num_hidden_layers"]
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (
        c["hidden_size"], c["intermediate_size"], c["vocab_size"])
    assert cfg.head_dim * cfg.num_heads == c["hidden_size"]
    assert cfg.rope_base == float(c["rope_theta"])
    with H.registered(cfg):
        from repro.configs import get_config
        assert get_config(name) is cfg
