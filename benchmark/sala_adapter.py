"""How the benchmark hands a configuration and seeded weights to the
program's MiniCPM-SALA (``paddle_tpu.models.sala``): the only place that
knows the program's parameter names."""
from __future__ import annotations

from . import sala_weights

_LEAF = {"n1": "input_layernorm.weight",
         "n2": "post_attention_layernorm.weight",
         "w1": "mlp.w1.weight", "w3": "mlp.w3.weight", "w2": "mlp.w2.weight",
         "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
         "v_w": "self_attn.v_proj.weight", "o_w": "self_attn.o_proj.weight",
         "gate_w": "self_attn.o_gate.weight",
         "z_w": "self_attn.z_proj.weight",
         "q_norm": "self_attn.q_norm.weight",
         "k_norm": "self_attn.k_norm.weight",
         "o_norm": "self_attn.o_norm.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}

#: the keys of the configuration file the program's SALAConfig takes as
#: published, and those it takes from the file's ``assumed`` block
CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "mixer_types", "num_attention_heads", "num_key_value_heads", "head_dim",
    "lightning_nh", "lightning_nkv", "lightning_head_dim", "lightning_scale",
    "lightning_use_rope", "attn_use_rope", "attention_bias", "qk_norm",
    "use_output_gate", "use_output_norm", "attn_use_output_gate",
    "hidden_act", "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
    "dim_model_base", "mup_denominator", "rand_init",
    "max_position_embeddings", "tie_word_embeddings", "model_type")
ASSUMED_KEYS = (
    "sparse_kernel_size", "sparse_kernel_stride", "sparse_block_size",
    "sparse_topk", "sparse_init_blocks", "sparse_window_size",
    "sparse_dense_len", "residual_depth")


def config_of(cfg: dict, **over):
    """The program's configuration object of a configuration file;
    ``over`` replaces keys (a control run's ``sparse_topk``)."""
    from paddle_tpu.models.sala import SALAConfig
    keys = {k: cfg[k] for k in CONFIG_KEYS}
    keys.update({k: cfg["assumed"][k] for k in ASSUMED_KEYS})
    keys.update(over)
    return SALAConfig(**keys)


def build_net(cfg: dict, **over):
    from paddle_tpu.models.sala import MiniCPMSALAForCausalLM
    return MiniCPMSALAForCausalLM(config_of(cfg, **over))


def load_weights(net, cfg: dict, seed: int):
    """Make the seeded weights a layer at a time and put each into the net
    as it is made."""
    params = dict(net.named_parameters())
    for name, value in sala_weights.make_top(cfg, seed).items():
        params.pop(_TOP[name]).set_value(value)
    for i in range(cfg["num_hidden_layers"]):
        for leaf, value in sala_weights.make_layer(cfg, seed, i).items():
            params.pop(f"model.layers.{i}.{_LEAF[leaf]}").set_value(value)
    if params:
        raise ValueError(f"parameters left unset: {sorted(params)}")
    sala_weights.clear_programs()
