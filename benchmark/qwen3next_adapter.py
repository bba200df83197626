"""How the benchmark hands a configuration and seeded weights to the
program's Qwen3-Next (``paddle_tpu.models.qwen3next``): the only place that
knows the program's parameter names."""
from __future__ import annotations

from . import qwen3next_weights

_LEAF = {"n1": "input_layernorm.weight",
         "n2": "post_attention_layernorm.weight",
         "router": "mlp.gate.weight",
         "w1": "mlp.experts.w1", "w3": "mlp.experts.w3",
         "w2": "mlp.experts.w2",
         "s1": "mlp.shared_experts.w1", "s3": "mlp.shared_experts.w3",
         "s2": "mlp.shared_experts.w2",
         "sg": "mlp.shared_expert_gate.weight",
         "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
         "v_w": "self_attn.v_proj.weight", "o_w": "self_attn.o_proj.weight",
         "q_norm": "self_attn.q_norm.weight",
         "k_norm": "self_attn.k_norm.weight",
         "qkvz_w": "linear_attn.in_proj_qkvz.weight",
         "ba_w": "linear_attn.in_proj_ba.weight",
         "conv_w": "linear_attn.conv1d.weight",
         "a_log": "linear_attn.A_log", "dt_bias": "linear_attn.dt_bias",
         "g_norm": "linear_attn.norm.weight",
         "out_w": "linear_attn.out_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}

#: the keys of the configuration file the program's Qwen3NextConfig takes as
#: they stand; ``num_experts`` and ``vocab_size`` count what is HELD in the
#: file and the whole model in the program, which takes the share beside them
CONFIG_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_hidden_layers",
    "full_attention_interval", "num_attention_heads", "num_key_value_heads",
    "head_dim", "partial_rotary_factor", "linear_conv_kernel_dim",
    "linear_key_head_dim", "linear_value_head_dim", "linear_num_key_heads",
    "linear_num_value_heads", "num_experts_per_tok", "norm_topk_prob",
    "decoder_sparse_step", "mlp_only_layers", "use_sliding_window",
    "hidden_act", "rms_norm_eps", "rope_theta", "rope_scaling",
    "max_position_embeddings", "tie_word_embeddings", "model_type")


def config_of(cfg: dict, config_cls=None):
    """The program's configuration object of a configuration file
    (``config_cls``: a control run's faulty subclass of it)."""
    from paddle_tpu.models.qwen3next import Qwen3NextConfig
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
                rope_theta=float(cfg["rope_theta"]),
                mlp_only_layers=tuple(cfg["mlp_only_layers"]))
    return (config_cls or Qwen3NextConfig)(**keys)


def build_net(cfg: dict, config_cls=None):
    from paddle_tpu.models.qwen3next import Qwen3NextForCausalLM
    return Qwen3NextForCausalLM(config_of(cfg, config_cls))


def program_name(i: int, leaf: str) -> str:
    """The program's parameter name of layer ``i``'s leaf ``leaf``."""
    return f"model.layers.{i}.{_LEAF[leaf]}"


def load_weights(net, cfg: dict, seed: int):
    """Make the seeded weights a layer at a time and put each into the net
    as it is made."""
    params = dict(net.named_parameters())
    for name, value in qwen3next_weights.make_top(cfg, seed).items():
        params.pop(_TOP[name]).set_value(value)
    for i in range(cfg["num_hidden_layers"]):
        for leaf, value in qwen3next_weights.make_layer(cfg, seed,
                                                        i).items():
            params.pop(program_name(i, leaf)).set_value(value)
    if params:
        raise ValueError(f"parameters left unset: {sorted(params)}")
    qwen3next_weights.clear_programs()
