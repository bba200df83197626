"""How the benchmark hands a configuration and seeded weights to the
program's GPT (``paddle_tpu.models``): the only place that knows the
program's parameter names."""
from __future__ import annotations

_LAYER = {"ln1_w": "norm1.weight", "ln1_b": "norm1.bias",
          "q_w": "self_attn.q_proj.weight", "q_b": "self_attn.q_proj.bias",
          "k_w": "self_attn.k_proj.weight", "k_b": "self_attn.k_proj.bias",
          "v_w": "self_attn.v_proj.weight", "v_b": "self_attn.v_proj.bias",
          "o_w": "self_attn.out_proj.weight",
          "o_b": "self_attn.out_proj.bias",
          "ln2_w": "norm2.weight", "ln2_b": "norm2.bias",
          "fc_w": "linear1.weight", "fc_b": "linear1.bias",
          "proj_w": "linear2.weight", "proj_b": "linear2.bias"}
_TOP = {"wte": "gpt.word_embeddings.weight",
        "wpe": "gpt.position_embeddings.weight",
        "lnf_w": "gpt.decoder.norm.weight", "lnf_b": "gpt.decoder.norm.bias"}


def program_name(name: str) -> str:
    """The program's parameter name of a weight of ``benchmark.weights``."""
    if name in _TOP:
        return _TOP[name]
    layer, leaf = name.split(".")
    return f"gpt.decoder.layers.{layer[1:]}.{_LAYER[leaf]}"


def build_net(cfg: dict, positions: int, recompute: bool = False):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    net = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"], max_position_embeddings=positions,
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0))
    if recompute:   # per block, as the repo's long-sequence flagship trains
        from paddle_tpu.distributed.fleet.utils import recompute as rc
        for blk in net.gpt.decoder.layers:
            blk.forward = (lambda *a, __f=blk.forward, **k: rc(__f, *a, **k))
    return net


def load_weights(net, weights: dict) -> dict:
    """Put the seeded weights into the net; returns ``{benchmark name:
    parameter}`` so that the caller can read parameters back by that name."""
    params = dict(net.named_parameters())
    by_name = {}
    for name, value in weights.items():
        p = params.pop(program_name(name))
        p.set_value(value)
        by_name[name] = p
    if params:
        raise ValueError(f"parameters left unset: {sorted(params)}")
    return by_name
