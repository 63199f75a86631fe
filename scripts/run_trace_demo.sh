#!/usr/bin/env bash
# Produce a demo comm-timeline trace from the MLP example workload on the
# 8-device CPU proof mesh: a few per-layer-sync training steps with the span
# ring armed (the default), dumped as Perfetto JSON and summarized in the terminal.
# Load the printed trace path in ui.perfetto.dev (or chrome://tracing) to see
# one track per request/bucket plus the trainer/dispatcher thread tracks.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT="${MLSL_TRACE_DIR:-/tmp/mlsl_trace_demo}"
mkdir -p "$OUT"

env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    MLSL_TRACE=1 MLSL_TRACE_DIR="$OUT" MLSL_STATS_DIR="$OUT" \
    python - <<'EOF'
import numpy as np
import jax

import mlsl_tpu as mlsl
from mlsl_tpu import obs
from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
from mlsl_tpu.models.train import DataParallelTrainer

env = mlsl.Environment.get_env().init()
dist = env.create_distribution(8, 1)
sess = env.create_session()
sess.set_global_minibatch_size(16)
trainer = DataParallelTrainer(
    env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS, get_layer,
    lr=0.1,
)
rng = np.random.default_rng(0)
for step in range(5):
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    loss = trainer.step(trainer.shard_batch(x, y))
    print(f"step {step}: loss {float(jax.device_get(loss).mean()):.4f}")
env.finalize()
path = obs.write_trace()
print(f"TRACE={path}")
EOF

TRACE=$(ls -t "$OUT"/trace-*.json | head -1)
echo
python scripts/trace_view.py "$TRACE" --tail 20
echo
echo "demo trace: $TRACE (load it in ui.perfetto.dev)"
