"""Calibration run of the cell ``serve-lfm2moe-decode``: the benchmark's own run
with the `high` and bfloat16 controls beside the program (a ``control:``
line), and with ``--probe`` a probe behind its comparison: the program's
forward (``FullSequence`` view, the Pallas kernels) against the reference
over the SAME rows the comparison reads (the sampled requests), by expert
layer: the largest router-score difference, the positions whose chosen sets
differ, the widest reference margin at such a flip and how many flips lie
inside a compared prefix (a ``probe:`` line). ``routing_margin_tau`` and the
readings of ``benchmark/limits/serve-lfm2moe-decode.json`` come from it
(PERF.md section 2). The probe shares the reference's weight arrays with
the program's parameter tree, so it needs no second copy of them.

    python3 tools/lfm2_routing_probe.py [--probe] --workload \
        serve-lfm2moe-decode --seed <n> --seconds 51 --trace 0
"""
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu  # noqa: E402,F401 -- sets the matmul precision the engine serves at
from benchmark import lfm2_adapter, run as bench_run
from benchmark.drivers import closed_lfm2
from benchmark.reference import lfm2_ref as ref
from paddle_tpu.models import lfm2 as prog
from paddle_tpu.ops import moe

NAMES = {"op_norm": "n1", "ffn_norm": "n2", "conv_in": "in", "conv_k": "k",
         "conv_out": "out", "q_w": "qw", "k_w": "kw", "v_w": "vw",
         "o_w": "ow", "q_norm": "qn", "k_norm": "kn", "gate": "gate",
         "expert_bias": "bias", "w1": "w1", "w3": "w3", "w2": "w2"}


def program_params(w, n_layers):
    layers = tuple({NAMES[k.split(".", 1)[1]]: v for k, v in w.items()
                    if k.startswith(f"l{i}.")} for i in range(n_layers))
    return {"tok": w["embed"], "fnw": w["final_norm"], "layers": layers}


def make_probe(cfg_dict):
    cfg = lfm2_adapter.config_of(cfg_dict)
    arch = ref.arch_of(cfg_dict)

    @jax.jit
    def program_scores(params, seq):
        stash = []
        real = moe.route_sigmoid_topk

        def spy(f, gate_w, bias, top_k, norm_topk=True, scale=1.0):
            stash.append(jax.nn.sigmoid(f @ gate_w) + bias)
            return real(f, gate_w, bias, top_k, norm_topk, scale)
        moe.route_sigmoid_topk = spy
        try:
            pos = jnp.arange(seq.shape[0], dtype=jnp.int32)[None]
            h, _ = prog.lfm2_hidden(cfg, params, seq[None], pos,
                                    prog.FullSequence())
        finally:
            moe.route_sigmoid_topk = real
        return jnp.stack(stash), h[0]            # [layers, S, E], [S, hidden]

    @jax.jit
    def reference_scores(w, seq):
        stash = []
        real = ref.route

        def spy(w_, p, f, arch_, mode):
            s = jax.nn.sigmoid(ref._ein("sh,he->se", f, w_[p + "gate"], mode))
            stash.append(s + w_[p + "expert_bias"])
            return real(w_, p, f, arch_, mode)
        ref.route = spy
        try:
            h, margin = ref.hidden_states(w, arch, seq)
        finally:
            ref.route = real
        return jnp.stack(stash), h, margin

    @jax.jit
    def compare(ps, rs):
        k = arch.top_k
        diff = jnp.max(jnp.abs(ps - rs), axis=-1)               # [L, S]
        chosen_p = ps >= jnp.sort(ps, -1)[..., -k][..., None]
        chosen_r = rs >= jnp.sort(rs, -1)[..., -k][..., None]
        flipped = jnp.any(chosen_p != chosen_r, axis=-1)        # [L, S]
        ranked = jnp.sort(rs, -1)
        margin = ranked[..., -k] - ranked[..., -k - 1]          # [L, S]
        return diff, flipped, margin

    return program_scores, reference_scores, compare


def install_probe():
    real = closed_lfm2.serve_gaps

    def probing(w, arch, sample, tau, pad_len, max_new, control_modes=()):
        out = real(w, arch, sample, tau, pad_len, max_new, control_modes)
        cfg_dict = probing.cfg
        program_scores, reference_scores, compare = make_probe(cfg_dict)
        params = program_params(w, cfg_dict["num_hidden_layers"])
        layers = cfg_dict["num_hidden_layers"] - cfg_dict["num_dense_layers"]
        worst = np.zeros(layers)
        decisions = flips = flips_compared = 0
        widest_flip_margin, hidden_gap = 0.0, 0.0
        for r in sample:
            plen, n = len(r["prompt"]), len(r["tokens"])
            seq = np.zeros(pad_len, np.int32)
            seq[:plen] = r["prompt"]
            seq[plen:plen + n - 1] = r["tokens"][:-1]
            used = plen + n - 1
            seq = jnp.asarray(seq)
            ps, hp = program_scores(params, seq)
            rs, hr, margin_min = reference_scores(w, seq)
            diff, flipped, margin = (np.asarray(x)[:, :used]
                                     for x in compare(ps, rs))
            worst = np.maximum(worst, diff.max(axis=1))
            decisions += diff.size
            flips += int(flipped.sum())
            if flipped.any():
                widest_flip_margin = max(widest_flip_margin,
                                         float(margin[flipped].max()))
            keep = closed_lfm2.compared_tokens(np.asarray(margin_min), plen,
                                               n, tau)
            # positions that predict a compared token, and those before them
            upto = plen - 1 + keep if keep else 0
            flips_compared += int(flipped[:, :upto].sum())
            hidden_gap = max(hidden_gap, float(jnp.max(jnp.abs(
                hp[:used] - hr[:used]))))
        print("probe: " + json.dumps({
            "requests": len(sample), "routing_decisions": decisions,
            "score_diff_max_by_layer": [float(x) for x in worst],
            "flips": flips, "widest_reference_margin_at_a_flip":
            widest_flip_margin, "flips_inside_compared_prefixes":
            flips_compared, "tau": tau, "hidden_gap_max": hidden_gap}),
            flush=True)
        return out
    closed_lfm2.serve_gaps = probing
    real_compare = closed_lfm2.ServedLFM2.compare

    def compare_with_cfg(self, run):
        probing.cfg = self.cfg
        return real_compare(self, run)
    closed_lfm2.ServedLFM2.compare = compare_with_cfg


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--probe" in argv:
        argv.remove("--probe")
        install_probe()
    sys.exit(bench_run.main(argv, control_modes=("high", "bfloat16")))
