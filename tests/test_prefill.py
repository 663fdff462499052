"""Chunked prefill: `transformer.prefill_chunk` against the teacher-forced
oracle (`serve_step.prefill_into_cache`), and the serving engine's chunk
path against offline greedy decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, tiny_config
from repro.models.registry import get_model
from repro.serve import engine as engine_mod
from repro.serve.engine import Request, ServeEngine
from repro.serve.serve_step import greedy_decode, prefill_into_cache

C = 8            # chunk of the model-level tests
MAX_LEN = 2 * C + 4   # a 2C+3 prompt's last chunk runs past it


def _model(arch):
    cfg = tiny_config(arch).scaled(dtype="float32")
    model = get_model(cfg)
    return cfg, model, model.init_params(jax.random.key(0))


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 512, n).astype(np.int32)


def _chunked(model, params, cache, prompt, slot, fn):
    """Prefill `prompt` into `slot` in chunks of C; the last chunk's
    token."""
    for s in range(0, len(prompt), C):
        n = min(C, len(prompt) - s)
        toks = np.zeros((C,), np.int32)
        toks[:n] = prompt[s:s + n]
        tok, cache = fn(params, cache, toks, np.int32(slot), np.int32(s),
                        np.int32(n))
    return int(tok), cache


def _oracle(model, params, cfg, prompt, max_len):
    logits, cache = prefill_into_cache(model, params,
                                       model.init_cache(1, max_len),
                                       jnp.asarray(prompt[None]))
    return int(jnp.argmax(logits[0, :cfg.vocab_size])), cache


def _rows_close(cache, ref, slot, p_len):
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a[:, slot, :p_len]),
                                   np.asarray(b[:, 0, :p_len]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("p_len", [1, C - 1, C, C + 1, 2 * C + 3])
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma2-27b"])
def test_prefill_chunk_matches_teacher_forcing(arch, p_len):
    """Cache rows [0, P) and the first token equal the decode path's;
    gemma2's tiny window (16) is shorter than the longest prompt."""
    cfg, model, params = _model(arch)
    prompt = _prompt(p_len, p_len)
    want, ref = _oracle(model, params, cfg, prompt, MAX_LEN)
    cache = model.init_cache(3, MAX_LEN)
    fn = jax.jit(model.prefill_chunk)
    tok, cache = _chunked(model, params, cache, prompt, 1, fn)
    assert tok == want
    _rows_close(cache, ref, 1, p_len)
    for leaf in jax.tree.leaves(cache):          # other slots untouched
        assert not np.asarray(leaf[:, 0]).any()
        assert not np.asarray(leaf[:, 2]).any()


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma2-27b"])
def test_prefill_chunk_readmitted_slot_ignores_stale_rows(arch):
    """A shorter prompt over a longer one's rows, with no reset: the
    rows it owns, its first token and the decode after it are its own."""
    cfg, model, params = _model(arch)
    fn = jax.jit(model.prefill_chunk, donate_argnums=(1,))
    cache = model.init_cache(2, MAX_LEN)
    _, cache = _chunked(model, params, cache, _prompt(2 * C + 3, 1), 0, fn)
    short = _prompt(C + 1, 2)
    tok, cache = _chunked(model, params, cache, short, 0, fn)
    want, ref = _oracle(model, params, cfg, short, MAX_LEN)
    assert tok == want
    _rows_close(cache, ref, 0, C + 1)
    # the next decode step reads kv_len = P + 1 rows of the stale cache
    pos = jnp.asarray([C + 1, 0], jnp.int32)
    got, _ = model.decode_step(params, cache,
                               jnp.asarray([tok, 0], jnp.int32), pos)
    exp, _ = model.decode_step(params, ref, jnp.asarray([want], jnp.int32),
                               jnp.int32(C + 1))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(exp[0]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_chunk_offered_only_where_exact(arch):
    cfg = get_config(arch)
    exact = not cfg.is_encoder_decoder and all(
        b.mixer in ("attn", "attn_local") and b.ffn in ("mlp", "none")
        for b in cfg.pattern)
    assert (get_model(cfg).prefill_chunk is not None) == exact


def test_engine_chunk_program_compiles_once(monkeypatch):
    """Prompts of several lengths (one to three chunks, one past
    max_len's last full chunk) into several slots: one program, and the
    engine's output is greedy decode's."""
    monkeypatch.setattr(engine_mod, "PREFILL_CHUNK", C)
    cfg, model, params = _model("qwen2-0.5b")
    max_len = 3 * C + 4
    eng = ServeEngine(model, params, batch_slots=3, max_len=max_len,
                      num_clients=1)
    lens = [1, C - 1, C, C + 1, 2 * C + 3, 3 * C + 1, 2]
    reqs = [Request(prompt=_prompt(n, 10 + n).tolist(), max_new_tokens=3)
            for n in lens]
    for r in reqs:
        eng.submit(r, 0)
    eng.run_until_drained()
    assert eng._prefill_fn._cache_size() == 1
    assert eng.stats["prefill_chunks"] == sum(-(-n // C) for n in lens)
    assert eng.stats["prefill_tokens"] == sum(lens)
    assert eng.stats["teacher_forced_tokens"] == 0
    for r in reqs:
        want = greedy_decode(model, params,
                             jnp.asarray([r.prompt], jnp.int32), 3, max_len)
        assert r.output == list(np.asarray(want[0])), len(r.prompt)


@pytest.mark.parametrize("arch", ["xlstm-125m", "jamba-v0.1-52b"])
def test_engine_recurrent_models_teacher_force(arch):
    cfg, model, params = _model(arch)
    assert model.prefill_chunk is None
    eng = ServeEngine(model, params, batch_slots=2, max_len=32,
                      num_clients=1)
    prompts = [[5, 9, 2], [7, 1], [3, 3, 3, 3]]
    reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
    for r in reqs:
        eng.submit(r, 0)
    eng.run_until_drained()
    assert eng.stats["teacher_forced_tokens"] == sum(map(len, prompts))
    assert eng.stats["prefill_chunks"] == eng.stats["prefill_tokens"] == 0
    for p, r in zip(prompts, reqs):
        want = greedy_decode(model, params, jnp.asarray([p], jnp.int32), 4,
                             32)
        assert r.output == list(np.asarray(want[0])), (p, r.output)


@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("offset", [0, 8, 13])
def test_attention_chunk_causal_rows_match_full_causal(offset, window):
    """Queries at offset..offset+S-1 against the whole key row give the
    full causal attention's rows there; keys past the chunk are unseen."""
    from repro.kernels import ref
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    s, t = C, 2 * C + 5
    q = jax.random.normal(k1, (1, t, 4, 16))
    k = jax.random.normal(k2, (1, t + s, 2, 16))
    v = jax.random.normal(k3, (1, t + s, 2, 16))
    want = ref.attention_ref(q, k[:, :t], v[:, :t], causal=True,
                             window=window)[:, offset:offset + s]
    got = ref.attention_ref(q[:, offset:offset + s], k, v, causal=True,
                            window=window, q_offset=jnp.int32(offset))
    n = min(s, t - offset)
    np.testing.assert_allclose(np.asarray(got[:, :n]),
                               np.asarray(want[:, :n]), rtol=1e-5,
                               atol=1e-5)
