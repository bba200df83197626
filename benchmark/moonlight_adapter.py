"""How the benchmark hands a configuration and seeded weights to the
program's Moonlight (``paddle_tpu.models.moonlight``): the only place that
knows the program's parameter names."""
from __future__ import annotations

from . import moonlight_weights

_LEAF = {"n1": "input_layernorm.weight",
         "n2": "post_attention_layernorm.weight",
         "q_w": "self_attn.q_proj.weight",
         "dkv_w": "self_attn.kv_a_proj_with_mqa.weight",
         "kv_norm": "self_attn.kv_a_layernorm.weight",
         "ukv_w": "self_attn.kv_b_proj.weight",
         "o_w": "self_attn.o_proj.weight",
         "router": "mlp.gate.weight", "expert_bias": "mlp.expert_bias",
         "s1": "mlp.shared_experts.w1", "s3": "mlp.shared_experts.w3",
         "s2": "mlp.shared_experts.w2"}
_DENSE = {m: f"mlp.{m}.weight" for m in ("w1", "w3", "w2")}
_EXPERTS = {m: f"mlp.experts.{m}" for m in ("w1", "w3", "w2")}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}

#: the keys of the configuration file the program's MoonlightConfig takes as
#: they stand; ``n_routed_experts`` and ``vocab_size`` count what is HELD in
#: the file and the whole model in the program, which takes the share beside
#: them
CONFIG_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "first_k_dense_replace", "moe_layer_freq",
    "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "topk_method", "scoring_func", "norm_topk_prob", "routed_scaling_factor",
    "seq_aux", "ep_size", "num_nextn_predict_layers", "attention_bias",
    "hidden_act", "rms_norm_eps", "rope_theta", "max_position_embeddings",
    "tie_word_embeddings", "model_type")


def config_of(cfg: dict, config_cls=None):
    """The program's configuration object of a configuration file
    (``config_cls``: a control run's faulty subclass of it)."""
    from paddle_tpu.models.moonlight import MoonlightConfig
    share = cfg["share"]
    if (cfg["n_routed_experts"], cfg["vocab_size"]) != (
            share["experts_held"][1], share["vocab_rows"][1]):
        raise ValueError("n_routed_experts and vocab_size count what is "
                         "held: they must be the share's")
    keys = {k: cfg[k] for k in CONFIG_KEYS}
    keys.update(n_routed_experts=share["num_experts_published"],
                experts_held=tuple(share["experts_held"]),
                vocab_size=share["vocab_size_published"],
                vocab_rows=tuple(share["vocab_rows"]),
                route_eps=cfg["assumed"]["route_eps"])
    return (config_cls or MoonlightConfig)(**keys)


def build_net(cfg: dict, config_cls=None):
    from paddle_tpu.models.moonlight import MoonlightForCausalLM
    return MoonlightForCausalLM(config_of(cfg, config_cls))


def program_name(cfg: dict, i: int, leaf: str) -> str:
    """The program's parameter name of layer ``i``'s leaf ``leaf``."""
    if leaf in ("w1", "w3", "w2"):
        table = _DENSE if i < cfg["first_k_dense_replace"] else _EXPERTS
        return f"model.layers.{i}.{table[leaf]}"
    return f"model.layers.{i}.{_LEAF[leaf]}"


def load_weights(net, cfg: dict, seed: int):
    """Make the seeded weights a layer at a time and put each into the net
    as it is made."""
    params = dict(net.named_parameters())
    for name, value in moonlight_weights.make_top(cfg, seed).items():
        params.pop(_TOP[name]).set_value(value)
    for i in range(cfg["num_hidden_layers"]):
        for leaf, value in moonlight_weights.make_layer(cfg, seed,
                                                        i).items():
            params.pop(program_name(cfg, i, leaf)).set_value(value)
    if params:
        raise ValueError(f"parameters left unset: {sorted(params)}")
    moonlight_weights.clear_programs()
