"""StarCoder2's published block against its plain reference, on the CPU.

The system (``repro.models`` with ``norm_type="layer"``, ``qkv_bias``,
``proj_bias``, ``embed_scale=False`` and a sliding window on every layer)
is compared with ``bench/lib/reference_starcoder2.py``, the plain float32
forward of the same block, on the reference's seeded weights (every bias
and LayerNorm parameter non-zero, the FFN weights n:m:g-exact).  The
config is StarCoder2-shaped but tiny, and its window of 8 is shorter than
the prompts, so the window bites.

The last tests pin bert-base-sten: the new config fields left at their
defaults add no operation to its programs and change none of its decode
logits (``tests/data/bert_parent_programs.json``, recorded from the code
before the fields existed by :func:`record`).
"""

import collections
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench.lib import reference_starcoder2 as reference  # noqa: E402
from bench.lib import weights  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data" / "bert_parent_programs.json"

#: StarCoder2's block at a tiny size: d 256, 8 query and 2 KV heads of 64,
#: d_ff 1024, 2 layers, a window of 8
REF_CFG = {
    "name": "starcoder2-tiny", "hidden_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 64,
    "intermediate_size": 1024, "vocab_size": 512,
    "hidden_act": "gelu_pytorch_tanh", "norm_type": "layer_norm",
    "norm_epsilon": 1e-5, "use_bias": True, "sliding_window": 8,
    "rope_theta": 100000.0,
}
LAYOUT = {"kind": "nmg", "n": 1, "m": 4, "g": 16, "gr": 64,
          "targets": ["mlp.wi", "mlp.wo"]}
WINDOW = REF_CFG["sliding_window"]

#: both sides compute in float32 from the same float32 weights; what is
#: left is summation order (the system's chunked online softmax and its
#: n:m:g products against the reference's dense products at highest
#: precision), a few 1e-6 of logits of order 1
ATOL = 1e-4


def _cfg():
    from repro.configs import get_arch

    return dataclasses.replace(
        get_arch("starcoder2-15b"), vocab=512, d_model=256, n_layers=2,
        n_heads=8, n_kv_heads=2, head_dim=64, d_ff=1024,
        local_window=WINDOW, dtype="float32")


def _params(seed: int = 7):
    """The reference's weights, stacked into the system's tree."""
    key = weights.seed_key(seed)
    params = reference.make_outer(key, REF_CFG, jnp.float32)
    layers = [reference.make_layer(weights.layer_key(key, i), REF_CFG,
                                   LAYOUT, jnp.float32)
              for i in range(REF_CFG["num_hidden_layers"])]
    params["layers"] = jax.tree_util.tree_map(lambda *x: jnp.stack(x),
                                              *layers)
    return key, params


def _ref_logits(key, toks):
    return np.asarray(reference.logits(key, REF_CFG, LAYOUT, jnp.float32,
                                       jnp.asarray(toks)), np.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, (1, n), np.int32)


def test_system_tree_is_the_reference_tree():
    from repro.models import init_lm

    _, params = _params()
    system = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), _cfg()))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(system)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(system)):
        assert a.shape == b.shape


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "nmg"])
def test_full_forward_matches_reference(sparse):
    from repro.models import forward, logits_of
    from repro.serve.engine import sparsify_for_serving

    cfg = _cfg()
    key, params = _params()
    if sparse:
        params = sparsify_for_serving(params, 1, 4, 16, gr=64)
    toks = _tokens(24)
    hidden, _ = forward(params, cfg, jnp.asarray(toks), remat="none")
    got = np.asarray(logits_of(params, cfg, hidden), np.float32)
    np.testing.assert_allclose(got, _ref_logits(key, toks), atol=ATOL,
                               rtol=0)


def test_window_changes_the_logits():
    """The comparison above sees the window: with it opened past the
    sequence the system's logits move far outside the tolerance."""
    from repro.models import forward, logits_of

    cfg = _cfg()
    key, params = _params()
    toks = _tokens(24)
    wide = dataclasses.replace(cfg, local_window=64)
    hidden, _ = forward(params, wide, jnp.asarray(toks), remat="none")
    got = np.asarray(logits_of(params, wide, hidden), np.float32)
    want = _ref_logits(key, toks)
    np.testing.assert_allclose(got[:, :WINDOW], want[:, :WINDOW], atol=ATOL,
                               rtol=0)
    assert np.abs(got - want).max() > 100 * ATOL


def test_paged_prefill_then_decode_matches_reference():
    """The serving path: n:m:g FFN weights with dense biases, a paged
    admission prefill of a prompt longer than the window, then
    teacher-forced decode steps through the paged cache; each step's
    logits against the reference's full forward at that position."""
    from repro.serve.cache import PagedKVCache
    from repro.serve.engine import _jit_paged_decode, sparsify_for_serving

    cfg = _cfg()
    key, dense = _params()
    params = sparsify_for_serving(dense, 1, 4, 16, gr=64)
    S, steps, slots = 20, 8, 2
    seq = _tokens(S + steps, seed=1)
    want = _ref_logits(key, seq)[0]
    kv = PagedKVCache(cfg, slots, 64, page_size=16)
    got = [np.asarray(kv.admit(params, jnp.asarray(seq[:, :S]), 1))[0]]
    step = _jit_paged_decode(cfg, kv.page_size, kv.num_pages)
    for t in range(steps - 1):
        p = S + t
        assert kv.ensure_writable_range(1, p, 1)
        tok = np.zeros((slots, 1), np.int32)
        tok[1, 0] = seq[0, p]
        pos = np.zeros(slots, np.int32)
        pos[1] = p
        logits, kv.data = step(params, jnp.asarray(tok), kv.data,
                               kv.device_table(), jnp.asarray(pos))
        got.append(np.asarray(logits)[1])
    np.testing.assert_allclose(np.stack(got), want[S - 1:S + steps - 1],
                               atol=ATOL, rtol=0)


def test_engine_counts_the_rows_the_window_hides():
    from repro.serve import Request, SamplingParams
    from repro.serve.engine import ServeEngine, window_masked_rows

    cfg = _cfg()
    _, dense = _params()
    eng = ServeEngine(dense, cfg, max_slots=2, max_seq_len=64, paged=True,
                      page_size=16, decode_chunk=4)
    reqs = [Request(uid=i, prompt=_tokens(L, seed=i)[0], max_new_tokens=6,
                    sampling=SamplingParams(greedy=True))
            for i, L in enumerate((5, 20))]
    eng.run(reqs)
    got = eng.stats["attn_window_masked_rows"]
    assert got > 0
    # by hand: a query at p hides max(0, p + 1 - 8) rows in each of the 2
    # layers; the 20-token prompt's queries, and the decode positions the
    # device ran (two chunks of 4 per request, from the prompt's end)
    hidden = [max(0, p + 1 - WINDOW) for p in range(20)]
    hidden += [max(0, p + 1 - WINDOW) for p in range(20, 28)]
    hidden += [max(0, p + 1 - WINDOW) for p in range(5, 13)]
    assert got == 2 * sum(hidden)
    assert window_masked_rows(cfg, 0, WINDOW) == 0
    assert window_masked_rows(_cfg().scaled(local_window=None), 0, 99) == 0


# -- bert-base-sten: no new op, the same logits ------------------------------


def _bert():
    from repro.configs import get_arch

    return dataclasses.replace(
        get_arch("bert-base-sten"), vocab=512, d_model=256, n_layers=2,
        n_heads=4, n_kv_heads=4, head_dim=64, d_ff=512)


def _primitive_counts(jaxpr) -> dict:
    """How often each primitive occurs in ``jaxpr`` and every jaxpr nested
    in its equations' parameters."""
    counts = collections.Counter()

    def walk(jx):
        for eqn in jx.eqns:
            counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return dict(sorted(counts.items()))


def record() -> dict:
    """bert-base-sten's serving programs at a small size (bf16, n:m:g FFN
    weights): the primitives of the paged prefill and decode chunk, and
    float32 decode logits of a teacher-forced ``decode_step`` loop."""
    from repro.models import decode_step, init_lm, prefill
    from repro.serve.cache import PagedKVCache, _jit_paged_prefill
    from repro.serve.engine import (_jit_paged_decode_chunk,
                                    sparsify_for_serving)

    cfg = _bert()
    params = sparsify_for_serving(init_lm(jax.random.PRNGKey(0), cfg),
                                  1, 4, 16, gr=64)
    kv = PagedKVCache(cfg, 4, 64, page_size=16)
    i32 = jnp.int32
    progs = {
        "jit_run": jax.make_jaxpr(
            _jit_paged_prefill(cfg, 16, kv.num_pages))(
            params, jnp.zeros((1, 16), i32), kv.data,
            jnp.zeros(4, i32), i32(0), i32(0)),
        "jit_chunk": jax.make_jaxpr(
            _jit_paged_decode_chunk(cfg, 16, kv.num_pages, 8))(
            params, jnp.zeros((4, 1), i32), kv.data,
            jnp.zeros((4, 4), i32), jnp.zeros(4, i32)),
    }
    f32 = dataclasses.replace(cfg, dtype="float32")
    p32 = init_lm(jax.random.PRNGKey(1), f32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 20), 0, 512,
                              jnp.int32)
    _, cache = prefill(p32, f32, toks[:, :16], cache_len=20)
    logits = []
    for i in range(4):
        lg, cache = decode_step(p32, f32, toks[:, 16 + i][:, None], cache,
                                jnp.asarray(16 + i))
        logits.append(np.asarray(lg, np.float32))
    return {"primitives": {k: _primitive_counts(v)
                           for k, v in progs.items()},
            "decode_logits_hex": np.stack(logits).tobytes().hex()}


@pytest.fixture(scope="module")
def bert_now():
    return record()


def test_bert_programs_gain_no_ops(bert_now):
    want = json.loads(DATA.read_text())["primitives"]
    assert bert_now["primitives"] == want


def test_bert_decode_logits_bitwise_the_parents(bert_now):
    want = json.loads(DATA.read_text())["decode_logits_hex"]
    got = bert_now["decode_logits_hex"]
    assert got == want, (
        np.abs(np.frombuffer(bytes.fromhex(got), np.float32)
               - np.frombuffer(bytes.fromhex(want), np.float32)).max())
