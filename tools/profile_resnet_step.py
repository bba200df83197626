"""Capture an xplane profile of the fused ResNet-50 train step and leave
the trace under /tmp/rsprof for xprof parsing (docs/perf_notes.md round-4
section). Run on the TPU host:

    PYTHONPATH=/root/repo:$PYTHONPATH python tools/profile_resnet_step.py
    JAX_PLATFORMS=cpu python - <<'PY'
    from xprof.convert import raw_to_tool_data as rtd
    import glob
    xp = sorted(glob.glob("/tmp/rsprof/**/*.xplane.pb", recursive=True))
    data, _ = rtd.xspace_to_tool_data(xp, "framework_op_stats", {})
    open("/tmp/framework_op_stats.out", "wb").write(data.encode())
    PY
"""

import os
import numpy as np
import jax, jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.optimizer as optim
from paddle_tpu.vision import models
from paddle_tpu.core import generator as _gen
from paddle_tpu.core.tensor import stable_uid

B = 256
paddle.seed(0)
net = models.resnet50(num_classes=1000)
opt = optim.Momentum(learning_rate=0.1, momentum=0.9,
                     parameters=net.parameters(), weight_decay=1e-4)
model = paddle.Model(net)
model.prepare(opt, paddle.nn.CrossEntropyLoss())
rng = np.random.RandomState(0)
x = paddle.to_tensor(rng.rand(B, 3, 224, 224).astype(np.float32))
y = paddle.to_tensor(rng.randint(0, 1000, (B,)).astype(np.int64))
with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
    model.train_batch([x], [y])
ts = model._train_step_fn
opt_states = [opt._state[stable_uid(p)] for p in ts["trainable"]]
train_raws = [p._data for p in ts["trainable"]]
fixed_raws = [ts["state"][i]._data for i in ts["fixed_pos"]]
lr = jnp.asarray(opt.get_lr(), jnp.float32)

def run(n, s0):
    global train_raws, opt_states
    loss = None
    for i in range(n):
        loss, _, train_raws, opt_states, _ = ts["fn"](
            train_raws, fixed_raws, opt_states, [x._data], [y._data],
            _gen.next_key(), lr, jnp.asarray(float(s0 + i), jnp.float32))
    return float(np.asarray(loss))

run(5, 3)  # warm
logdir = "/tmp/rsprof"
os.system(f"rm -rf {logdir}")
with jax.profiler.trace(logdir):
    run(10, 10)
print("trace done")
