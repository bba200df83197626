"""How the benchmark hands a configuration and seeded weights to the
program's Trinity (``paddle_tpu.models.trinity``): the only place that knows
the program's parameter names."""
from __future__ import annotations

from . import trinity_weights

_LEAF = {"n1": "input_layernorm.weight",
         "n2": "post_attention_layernorm.weight",
         "n3": "pre_mlp_layernorm.weight", "n4": "post_mlp_layernorm.weight",
         "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
         "v_w": "self_attn.v_proj.weight",
         "gate_w": "self_attn.gate_proj.weight",
         "o_w": "self_attn.o_proj.weight",
         "q_norm": "self_attn.q_norm.weight",
         "k_norm": "self_attn.k_norm.weight",
         "router": "mlp.gate.weight", "expert_bias": "mlp.expert_bias",
         "s1": "mlp.shared_experts.w1", "s3": "mlp.shared_experts.w3",
         "s2": "mlp.shared_experts.w2"}
_DENSE = {m: f"mlp.{m}.weight" for m in ("w1", "w3", "w2")}
_EXPERTS = {m: f"mlp.experts.{m}" for m in ("w1", "w3", "w2")}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}

#: the keys of the configuration file the program's TrinityConfig takes as
#: they stand; ``num_experts`` and ``vocab_size`` count what is HELD in the
#: file and the whole model in the program, which takes the share beside them
CONFIG_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "layer_types", "global_attn_every_n_layers",
    "sliding_window", "num_attention_heads", "num_key_value_heads",
    "head_dim", "num_dense_layers", "num_experts_per_tok",
    "num_shared_experts", "num_expert_groups", "num_limited_groups",
    "n_group", "topk_group", "score_func", "route_norm", "route_scale",
    "load_balance_coeff", "use_grouped_mm", "mup_enabled", "hidden_act",
    "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings",
    "tie_word_embeddings", "model_type")


def config_of(cfg: dict, **over):
    """The program's configuration object of a configuration file; ``over``
    replaces keys (a control run's ``sliding_window``)."""
    from paddle_tpu.models.trinity import TrinityConfig
    share = cfg["share"]
    if (cfg["num_experts"], cfg["vocab_size"]) != (
            share["experts_held"][1], share["vocab_rows"][1]):
        raise ValueError("num_experts and vocab_size count what is held: "
                         "they must be the share's")
    keys = {k: cfg[k] for k in CONFIG_KEYS}
    keys.update(num_experts=share["num_experts_published"],
                experts_held=tuple(share["experts_held"]),
                vocab_size=share["vocab_size_published"],
                vocab_rows=tuple(share["vocab_rows"]),
                route_eps=cfg["assumed"]["route_eps"])
    keys.update(over)
    return TrinityConfig(**keys)


def build_net(cfg: dict, **over):
    from paddle_tpu.models.trinity import TrinityForCausalLM
    return TrinityForCausalLM(config_of(cfg, **over))


def program_name(cfg: dict, i: int, leaf: str) -> str:
    """The program's parameter name of layer ``i``'s leaf ``leaf``."""
    if leaf in ("w1", "w3", "w2"):
        table = _DENSE if i < cfg["num_dense_layers"] else _EXPERTS
        return f"model.layers.{i}.{table[leaf]}"
    return f"model.layers.{i}.{_LEAF[leaf]}"


def load_weights(net, cfg: dict, seed: int):
    """Make the seeded weights a layer at a time and put each into the net
    as it is made. A net built without the shared expert (a control) leaves
    that expert's leaves out."""
    params = dict(net.named_parameters())
    shared = bool(net.config.num_shared_experts)
    for name, value in trinity_weights.make_top(cfg, seed).items():
        params.pop(_TOP[name]).set_value(value)
    for i in range(cfg["num_hidden_layers"]):
        for leaf, value in trinity_weights.make_layer(cfg, seed, i).items():
            if shared or leaf not in ("s1", "s3", "s2"):
                params.pop(program_name(cfg, i, leaf)).set_value(value)
    if params:
        raise ValueError(f"parameters left unset: {sorted(params)}")
    trinity_weights.clear_programs()
