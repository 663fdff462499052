"""A dense decoder-only transformer (Qwen2 and its kin) as the program
runs it: grouped-query attention with optional q/k/v biases, a SiLU-gated
MLP, RMSNorm, every layer alike.

An architecture file is found by the `"architecture"` key of a
configuration file (`archs/<architecture>.py`) and gives the drivers
everything they know of one kind of model:

    model_config(c)          the program's ModelConfig
    weight_shapes(c)         canonical name -> (shape, std, mean) of the
                             weights held on this chip, layers stacked on
                             the first axis of every leaf but those in
                             UNSTACKED; `benchlib.weights.generate` makes
                             them from the seed
    UNSTACKED                the names of the leaves not stacked by layer
                             (the train check's per-leaf norms,
                             `benchlib.weights.leaf_norms`, take them)
    to_program(model, w)     canonical weights -> the program's parameter
                             tree (traceable)
    from_program(tree)       the program's tree -> canonical names
    counts(c)                the operations the algorithm needs:
                             `token_flops(context)`,
                             `decode_step_flops(contexts)`,
                             `train_step_flops(batch, seq)`,
                             `matmul_params`, and the attention's `heads`,
                             `kv_heads`, `head_dim`
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchlib.flops import Decoder

UNSTACKED = ("embed", "final_norm")


def model_config(c: dict):
    """The program's ModelConfig of a dense decoder configuration file."""
    from repro.models.config import BlockSpec, ModelConfig
    return ModelConfig(
        name=c["name"], family="dense",
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        pattern=(BlockSpec(mixer="attn", ffn="mlp"),),
        repeats=c["num_hidden_layers"], head_dim=c.get("head_dim"),
        qkv_bias=c["qkv_bias"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"])


def weight_shapes(c: dict) -> Dict[str, Tuple[tuple, float, float]]:
    """name -> (shape, std, mean) of the canonical weights, layers
    stacked on the first axis. Biases and norm scales are random too, so
    that every path of the block carries signal."""
    m = counts(c)
    L, d, f = m.layers, m.d_model, m.d_ff
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {
        "embed": ((m.vocab, d), 0.02, 0.0),
        "ln1": ((L, d), 0.1, 1.0),
        "wq": ((L, d, q), d ** -0.5, 0.0),
        "bq": ((L, q), 0.1, 0.0),
        "wk": ((L, d, kv), d ** -0.5, 0.0),
        "bk": ((L, kv), 0.1, 0.0),
        "wv": ((L, d, kv), d ** -0.5, 0.0),
        "bv": ((L, kv), 0.1, 0.0),
        "wo": ((L, q, d), q ** -0.5, 0.0),
        "ln2": ((L, d), 0.1, 1.0),
        "w_gate": ((L, d, f), d ** -0.5, 0.0),
        "w_up": ((L, d, f), d ** -0.5, 0.0),
        "w_down": ((L, f, d), f ** -0.5, 0.0),
        "final_norm": ((d,), 0.1, 1.0),
    }


def to_program(model, w: dict) -> dict:
    """Canonical weights -> the program's parameter tree (traceable).
    The program pads the vocabulary; its padding rows are zero."""
    import jax
    import jax.numpy as jnp
    spec = jax.eval_shape(model.init_params, jax.random.key(0))
    vpad = spec["embed"]["embedding"].shape[0]
    emb = jnp.zeros((vpad, w["embed"].shape[1]), w["embed"].dtype)
    emb = emb.at[:w["embed"].shape[0]].set(w["embed"])
    layer = {"norm_mixer": {"scale": w["ln1"]},
             "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo",
                                          "bq", "bk", "bv")},
             "norm_ffn": {"scale": w["ln2"]},
             "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    params = {"embed": {"embedding": emb}, "layers": (layer,),
              "final_norm": {"scale": w["final_norm"]}}
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), spec)
    if got != want:
        raise ValueError(f"weights do not fit the program: {got} != {want}")
    return params


def from_program(tree: dict) -> dict:
    """The program's parameter tree (or a tree shaped like it) by
    canonical name; the inverse of to_program but for the padding."""
    layer = tree["layers"][0]
    out = {"embed": tree["embed"]["embedding"],
           "ln1": layer["norm_mixer"]["scale"],
           "ln2": layer["norm_ffn"]["scale"],
           "final_norm": tree["final_norm"]["scale"]}
    out.update(layer["mixer"])
    out.update(layer["ffn"])
    return out


def counts(c: dict) -> Decoder:
    return Decoder.from_config(c)
