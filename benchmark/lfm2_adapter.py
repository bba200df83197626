"""How the benchmark hands a configuration and seeded weights to the
program's LFM2 (``paddle_tpu.models.lfm2``): the only place that knows the
program's parameter names."""
from __future__ import annotations

from . import lfm2_weights

_LEAF = {"op_norm": "operator_norm.weight", "ffn_norm": "ffn_norm.weight",
         "conv_in": "conv.in_proj.weight", "conv_k": "conv.conv.weight",
         "conv_out": "conv.out_proj.weight",
         "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
         "v_w": "self_attn.v_proj.weight", "o_w": "self_attn.out_proj.weight",
         "q_norm": "self_attn.q_layernorm.weight",
         "k_norm": "self_attn.k_layernorm.weight",
         "gate": "feed_forward.gate.weight",
         "expert_bias": "feed_forward.expert_bias"}
_DENSE = {m: f"feed_forward.{m}.weight" for m in ("w1", "w3", "w2")}
_EXPERTS = {m: f"feed_forward.experts.{m}" for m in ("w1", "w3", "w2")}
_TOP = {"embed": "model.embed_tokens.weight",
        "final_norm": "model.embedding_norm.weight"}

#: the keys of the configuration file the program's LFM2Config takes
CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "use_expert_bias", "conv_L_cache", "conv_bias", "norm_eps", "rope_theta",
    "max_position_embeddings", "model_type")


def program_name(cfg: dict, i: int, leaf: str) -> str:
    """The program's parameter name of layer ``i``'s leaf ``leaf``."""
    if leaf in ("w1", "w3", "w2"):
        table = _DENSE if i < cfg["num_dense_layers"] else _EXPERTS
        return f"model.layers.{i}.{table[leaf]}"
    return f"model.layers.{i}.{_LEAF[leaf]}"


def config_of(cfg: dict):
    """The program's configuration object of a configuration file."""
    from paddle_tpu.models.lfm2 import LFM2Config
    return LFM2Config(
        tie_word_embeddings=bool(cfg["assumed"]["tie_word_embeddings"]),
        **{k: cfg[k] for k in CONFIG_KEYS})


def build_net(cfg: dict):
    from paddle_tpu.models.lfm2 import LFM2ForCausalLM
    return LFM2ForCausalLM(config_of(cfg))


def load_weights(net, cfg: dict, seed: int):
    """Make the seeded weights a layer at a time and put each into the net
    as it is made."""
    params = dict(net.named_parameters())
    for name, value in lfm2_weights.make_top(cfg, seed).items():
        params.pop(_TOP[name]).set_value(value)
    for i in range(cfg["num_hidden_layers"]):
        for leaf, value in lfm2_weights.make_layer(cfg, seed, i).items():
            params.pop(program_name(cfg, i, leaf)).set_value(value)
    if params:
        raise ValueError(f"parameters left unset: {sorted(params)}")
