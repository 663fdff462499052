"""How the benchmark hands a configuration to the program under test:
its `ModelConfig`, and the canonical weights laid out as its parameter
tree."""
from __future__ import annotations

from .flops import Decoder
from .weights import decoder_weights


def model_config(c: dict):
    """The program's ModelConfig of a dense decoder configuration file."""
    from repro.models.config import BlockSpec, ModelConfig
    if c["architecture"] != "dense_decoder":
        raise ValueError(f"unsupported architecture {c['architecture']!r}")
    return ModelConfig(
        name=c["name"], family="dense",
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        pattern=(BlockSpec(mixer="attn", ffn="mlp"),),
        repeats=c["num_hidden_layers"], head_dim=c.get("head_dim"),
        qkv_bias=c["qkv_bias"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"])


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


def program_params(model, c: dict, seed: int):
    """The program's parameters, made on the device in one jitted call."""
    return decoder_weights(Decoder.from_config(c), seed, c["torch_dtype"],
                           convert=lambda w: to_program(model, w))


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

