"""Quickstart: train a tiny LM for 30 steps, checkpoint it, generate.

    PYTHONPATH=src python examples/quickstart.py

Smoke knobs (used by tests/test_examples.py to keep the example cheap):
QUICKSTART_STEPS, QUICKSTART_GEN_STEPS, QUICKSTART_CKPT_DIR.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

STEPS = int(os.environ.get("QUICKSTART_STEPS", "30"))
GEN_STEPS = int(os.environ.get("QUICKSTART_GEN_STEPS", "16"))
CKPT_DIR = os.environ.get("QUICKSTART_CKPT_DIR",
                          os.path.join(tempfile.gettempdir(), "repro_quickstart"))

from repro.ckpt.manager import CheckpointManager
from repro.data.pipeline import DataConfig, Pipeline
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.serve.lm_engine import ServeEngine
from repro.train import step as ts


def main():
    cfg = ModelConfig(name="quickstart", family="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
                      d_ff=512, vocab=512, param_dtype="float32",
                      compute_dtype="float32")
    opt = AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=200)
    state = ts.init_state(jax.random.PRNGKey(0), cfg, opt)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"model: {n_params/1e6:.1f}M params")

    pipe = Pipeline(cfg, DataConfig(global_batch=8, seq_len=128, seed=0))
    train = jax.jit(ts.make_train_step(cfg, opt))
    for i in range(STEPS):
        state, m = train(state, pipe.batch(i))
        if i % 5 == 0:
            print(f"step {i:3d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}")

    mgr = CheckpointManager(CKPT_DIR, every=1, async_save=False)
    mgr.maybe_save(STEPS, state, force=True)
    print("checkpointed:", mgr.latest_step())

    engine = ServeEngine(cfg=cfg, params=state.params, max_len=160)
    prompts = pipe.batch(0)["tokens"][:2, :16]
    out = engine.generate(prompts, num_steps=GEN_STEPS)
    print("generated:", out[0].tolist())


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
