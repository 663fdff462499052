"""Launch layer on the CPU: where the persistent compilation cache goes
(and that importing the package sets none), and a serve step that never
emits an id from the vocabulary's padding."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.launch import compile_cache
from repro.models.layers import padded_vocab
from repro.models.registry import get_model
from repro.serve.engine import Request, ServeEngine

ROOT = Path(__file__).resolve().parents[1]

# child process: import the launchers, check that no cache directory was
# set by the imports, turn the cache on (the fixed path redirected into
# the test's tmp dir) and compile one program
_CHILD = """
import sys
import jax, jax.numpy as jnp
import repro.launch.serve, repro.launch.train
from repro.launch import compile_cache
print("at_import", jax.config.jax_compilation_cache_dir)
compile_cache.CACHE_DIR = compile_cache.Path(sys.argv[1])
print("in_use", compile_cache.use_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) @ x).lower(jnp.ones((8, 8))).compile()
"""


def test_cache_dir_is_fixed_inside_the_checkout():
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_only_in_the_chosen_dir(tmp_path, env_set):
    fixed, from_env = tmp_path / "fixed", tmp_path / "from_env"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(from_env)
    out = subprocess.run([sys.executable, "-c", _CHILD, str(fixed)],
                         env=env, capture_output=True, text=True,
                         timeout=240, check=True).stdout.splitlines()
    chosen, other = (from_env, fixed) if env_set else (fixed, from_env)
    assert out == [f"at_import {from_env if env_set else None}",
                   f"in_use {chosen}"]
    assert any(chosen.iterdir())
    assert not other.exists()


def test_serve_emits_no_padded_vocab_ids():
    cfg = tiny_config("qwen2-0.5b").scaled(vocab_size=300)
    assert padded_vocab(cfg) == 512        # 212 padding columns
    model = get_model(cfg)
    eng = ServeEngine(model, model.init_params(jax.random.key(3)),
                      batch_slots=2, max_len=32, num_clients=1)
    rng = np.random.RandomState(0)
    for _ in range(4):
        eng.submit(Request(prompt=rng.randint(1, 300, 3).tolist(),
                           max_new_tokens=8))
    eng.run_until_drained()
    ids = [t for r in eng.completed for t in r.output]
    assert len(eng.completed) == 4 and len(ids) == 32
    assert 0 <= min(ids) and max(ids) < cfg.vocab_size
