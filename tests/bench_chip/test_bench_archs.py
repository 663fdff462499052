"""The dense decoder's architecture file: its weights are the stream the
benchmark has always drawn, bit for bit, and its counts are `Decoder`'s."""
import hashlib
import json

import numpy as np
import pytest

from benchpath import BENCH
from benchlib.flops import Decoder
from benchlib.manifest import load_architecture
from benchlib.weights import generate

DENSE = load_architecture("dense_decoder")
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=300)
# sha256 (first 16 hex digits) of each bfloat16 leaf that the generator
# drew, at seed 2**31 + 99, for the qwen2 configuration cut to TINY,
# before the dense decoder's shapes moved into archs/dense_decoder.py
STREAM = {
    "bk": "19c2d0ab2f53b19b", "bq": "1c867abf2faf63bd",
    "bv": "09045fa03297e51f", "embed": "3f81c9fac6ed833f",
    "final_norm": "38f52f082bcd339c", "ln1": "056633fc021975b0",
    "ln2": "d922582e594c0cd1", "w_down": "54749a2f9dc37789",
    "w_gate": "a689a29b18c6dcd7", "w_up": "c91d55a7e98e78bf",
    "wk": "f213c0e064ac6ed4", "wo": "90c475ef0bf5fc97",
    "wq": "ad4fe8a4729ff7da", "wv": "8ff7ac9528d3411c",
}


def qwen2(**over):
    c = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    c.update(over)
    return c


def test_weights_are_the_stream_drawn_before():
    c = qwen2(**TINY)
    w = generate(DENSE.weight_shapes(c), 2 ** 31 + 99, c["torch_dtype"])
    got = {k: hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
           for k, a in w.items()}
    assert got == STREAM


def test_program_tree_round_trips_the_canonical_weights():
    import jax
    from repro.models.registry import get_model
    c = qwen2(**TINY)
    model = get_model(DENSE.model_config(c))
    shapes = DENSE.weight_shapes(c)
    w = generate(shapes, 7, c["torch_dtype"])
    tree = generate(shapes, 7, c["torch_dtype"],
                    convert=lambda x: DENSE.to_program(model, x))
    back = DENSE.from_program(tree)
    back["embed"] = back["embed"][:c["vocab_size"]]
    assert sorted(back) == sorted(w)
    for k in w:
        assert np.array_equal(np.asarray(back[k]), np.asarray(w[k])), k
    spec = jax.eval_shape(model.init_params, jax.random.key(0))
    assert jax.tree.structure(tree) == jax.tree.structure(spec)


def test_counts_are_the_decoders():
    c = qwen2()
    m, d = DENSE.counts(c), Decoder.from_config(c)
    ctx = [1, 255, 1024]
    assert m.decode_step_flops(ctx) == d.decode_step_flops(ctx)
    assert m.train_step_flops(8, 1024) == d.train_step_flops(8, 1024)
    assert m.matmul_params == d.matmul_params


def test_leaf_norms_split_only_the_stacked_leaves():
    import jax.numpy as jnp
    from benchlib.weights import leaf_norms
    w = {"embed": jnp.full((3, 2), 2.0), "head": jnp.ones((5, 4)),
         "wq": jnp.stack([jnp.ones((4, 4)), 3 * jnp.ones((4, 4))])}
    got = {k: float(v) for k, v in leaf_norms(w, ("embed", "head")).items()}
    assert got == pytest.approx({"embed": 2.0 * 6 ** 0.5, "head": 20 ** 0.5,
                                 "wq.0": 4.0, "wq.1": 12.0})
