"""Transformer family suite (ISSUE 12): decoder-only block semantics
(shapes, causality, tied head), the prefill/decode_step serving protocol's
parity with the full forward, the SNIPPETS.md [2] partition metadata, zoo
registration, and logit parity against a torch reference module through
``convert_transformer_state_dict``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn as tnn

from tpuddp.models import TransformerLM, _REGISTRY, load_model
from tpuddp.models.torch_import import convert_transformer_state_dict
from tpuddp.models.transformer import (
    PARTITION_RULES,
    param_logical_axes,
    partition_spec,
    prefill_buckets,
)
from tpuddp.nn.core import Context

KEY = jax.random.key(0)
CTX = Context(train=False)

V, E, H, L, T = 32, 16, 4, 2, 24  # tiny: every compile trivial


@pytest.fixture(scope="module")
def model():
    return TransformerLM(
        num_classes=V, d_model=E, n_heads=H, n_layers=L, max_seq_len=T
    )


@pytest.fixture(scope="module")
def params(model):
    p, state = model.init(KEY, jnp.zeros((1, 2), jnp.int32))
    assert state == ()
    return p


def _tokens(rng, b, t):
    return jnp.asarray(rng.randint(0, V, size=(b, t)), jnp.int32)


# ----------------------------------------------------------------- forward --


def test_apply_shapes_and_dtype(model, params):
    rng = np.random.RandomState(0)
    logits, state = model.apply(params, (), _tokens(rng, 3, 7), CTX)
    assert logits.shape == (3, 7, V)
    assert logits.dtype == jnp.float32
    assert state == ()


def test_apply_rejects_overlong_sequence(model, params):
    with pytest.raises(ValueError, match="max_seq_len"):
        model.apply(params, (), jnp.zeros((1, T + 1), jnp.int32), CTX)


def test_causal_mask_blocks_future_positions(model, params):
    """Logits at position t must be a function of tokens[0..t] only: editing
    every token AFTER t cannot move them (the autoregressive contract the
    decode engine's bitwise guarantee is built on)."""
    rng = np.random.RandomState(1)
    toks = np.asarray(_tokens(rng, 1, 10))
    logits, _ = model.apply(params, (), jnp.asarray(toks), CTX)
    edited = toks.copy()
    edited[0, 6:] = (edited[0, 6:] + 7) % V
    logits2, _ = model.apply(params, (), jnp.asarray(edited), CTX)
    np.testing.assert_array_equal(
        np.asarray(logits[0, :6]), np.asarray(logits2[0, :6])
    )
    assert not np.array_equal(np.asarray(logits[0, 6:]), np.asarray(logits2[0, 6:]))


def test_lm_head_is_tied_to_embedding(params):
    """No separate head matrix anywhere in the tree — logits must come from
    embed.weight itself (the GPT-2 tying convention the importer enforces)."""
    leaves = jax.tree_util.tree_leaves_with_path(params)
    names = ["/".join(str(k) for k in path) for path, _ in leaves]
    assert not any("head" in n for n in names)


def test_batch_rows_independent(model, params):
    """Row b's logits must not depend on what else shares the batch."""
    rng = np.random.RandomState(2)
    toks = _tokens(rng, 4, 8)
    full, _ = model.apply(params, (), toks, CTX)
    solo, _ = model.apply(params, (), toks[1:2], CTX)
    np.testing.assert_array_equal(np.asarray(full[1]), np.asarray(solo[0]))


# ------------------------------------------------- prefill / decode_step --


def _pool_pair(model, num_blocks=16, block_size=4):
    shape = (model.n_layers, num_blocks, block_size, model.n_heads,
             model.head_dim)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def test_prefill_matches_full_forward_last_position(model, params):
    """The serving prefill (bucketed length, paged-pool commit) must produce
    the full forward's last-position logits — the two code paths share the
    block math, and this pins that they cannot drift. To float32 rounding,
    not bitwise: the prefill runs the same products at the padded bucket's
    length (8 rows for 5), and XLA's CPU matmul sums a row's products in
    another order at another shape, which moves a logit of size ~1 by a few
    1e-7 (3 ulp). A wrong position, mask or pool row moves it by 1e-2 or
    more, so 1e-5 keeps the test's point."""
    rng = np.random.RandomState(3)
    n = 5
    prompt = np.asarray(_tokens(rng, 1, n))
    kpool, vpool = _pool_pair(model)
    table_row = jnp.asarray([1, 2, 3, 0, 0, 0], jnp.int32)
    P = 8  # the padded bucket
    buf = np.zeros((1, P), np.int32)
    buf[0, :n] = prompt[0]
    last, kpool, vpool = model.prefill(
        params, kpool, vpool, table_row, jnp.asarray(buf),
        jnp.asarray(n, jnp.int32),
    )
    ref, _ = model.apply(params, (), jnp.asarray(prompt), CTX)
    np.testing.assert_allclose(np.asarray(last), np.asarray(ref[0, n - 1]), rtol=1e-5, atol=1e-5)


def test_prefill_plus_steps_match_full_forward(model, params):
    """Greedy decode through prefill + fixed-shape steps must equal greedy
    decode through repeated full forwards — KV paging is numerically
    invisible at the model level, not just end to end."""
    rng = np.random.RandomState(4)
    n, steps, S, BS = 4, 5, 3, 4
    prompt = np.asarray(_tokens(rng, 1, n))
    kpool, vpool = _pool_pair(model, num_blocks=16, block_size=BS)
    max_blocks = 6
    tables = np.zeros((S, max_blocks), np.int32)
    tables[1, :3] = [4, 5, 6]  # the sequence under test lives in slot 1
    lengths = np.zeros((S,), np.int32)
    buf = np.zeros((1, 8), np.int32)
    buf[0, :n] = prompt[0]
    last, kpool, vpool = model.prefill(
        params, kpool, vpool, jnp.asarray(tables[1]), jnp.asarray(buf),
        jnp.asarray(n, jnp.int32),
    )
    lengths[1] = n
    got = [int(np.argmax(np.asarray(last)))]
    for _ in range(steps):
        toks = np.zeros((S,), np.int32)
        toks[1] = got[-1]
        logits, kpool, vpool = model.decode_step(
            params, kpool, vpool, jnp.asarray(tables), jnp.asarray(lengths),
            jnp.asarray(toks),
        )
        lengths[1] += 1
        got.append(int(np.argmax(np.asarray(logits)[1])))
    # reference: greedy decode via the full forward, re-running the whole
    # growing sequence every step
    seq = list(prompt[0])
    ref = []
    for _ in range(steps + 1):
        logits, _ = model.apply(
            params, (), jnp.asarray([seq], jnp.int32), CTX
        )
        tok = int(np.argmax(np.asarray(logits)[0, -1]))
        ref.append(tok)
        seq.append(tok)
    assert got == ref


# ------------------------------------------------------ partition metadata --


def test_param_logical_axes_congruent_with_params(model, params):
    axes = param_logical_axes(model, params)
    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(n, str) for n in x
    )
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_a = jax.tree_util.tree_leaves_with_path(axes, is_leaf=is_leaf)
    assert [p for p, _ in flat_a] == [p for p, _ in flat_p]
    for (_, names), (path, leaf) in zip(flat_a, flat_p):
        assert len(names) == leaf.ndim, (path, names, leaf.shape)
        assert all(n in PARTITION_RULES for n in names)


def test_partition_spec_follows_snippet_rule_table(model, params):
    """The tensor-parallel split of SNIPPETS.md [2]: joined QKV column-split,
    attention output row-split, MLP up column-/down row-split on the "model"
    axis; embeddings, norms, and biases on unsharded logical axes."""
    spec = partition_spec(model, params)
    blk = spec["blocks"][0]
    assert blk["attn"]["wqkv"] == (None, "model")  # joined_kv
    assert blk["attn"]["bqkv"] == ("model",)
    assert blk["attn"]["wo"] == ("model", None)  # heads contraction
    assert blk["mlp"]["w1"] == (None, "model")
    assert blk["mlp"]["w2"] == ("model", None)
    assert blk["mlp"]["b1"] == ("model",)
    assert spec["embed"]["weight"] == (None, None)
    assert spec["pos"]["weight"] == (None, None)
    assert spec["ln_f"]["scale"] == (None,)
    # a custom rule table routes through unchanged
    alt = partition_spec(model, params, rules={**PARTITION_RULES, "mlp": "x"})
    assert alt["blocks"][0]["mlp"]["w1"] == (None, "x")


def test_prefill_buckets_ladder():
    assert prefill_buckets(63) == [1, 2, 4, 8, 16, 32, 63]
    assert prefill_buckets(64) == [1, 2, 4, 8, 16, 32, 64]


# ----------------------------------------------------------- zoo + import --


def test_zoo_registration_and_vocab_alias():
    assert "transformer_tiny" in _REGISTRY
    assert "transformer_small" in _REGISTRY
    m = load_model("transformer_tiny", num_classes=100)
    assert isinstance(m, TransformerLM)
    assert m.vocab_size == 100  # num_classes aliases vocab_size


def test_bad_head_split_rejected():
    with pytest.raises(ValueError, match="divisible"):
        TransformerLM(d_model=10, n_heads=3)


class _TorchBlock(tnn.Module):
    def __init__(self, E, H, F):
        super().__init__()
        self.ln1 = tnn.LayerNorm(E)
        self.attn = tnn.Module()
        self.attn.in_proj = tnn.Linear(E, 3 * E)
        self.attn.out_proj = tnn.Linear(E, E)
        self.ln2 = tnn.LayerNorm(E)
        self.mlp = tnn.Module()
        self.mlp.fc1 = tnn.Linear(E, F)
        self.mlp.fc2 = tnn.Linear(F, E)
        self.H = H

    def forward(self, h):
        B, T, E = h.shape
        a = self.ln1(h)
        qkv = self.attn.in_proj(a).reshape(B, T, 3, self.H, E // self.H)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = torch.einsum("bqhd,bkhd->bhqk", q, k) / (E // self.H) ** 0.5
        mask = torch.tril(torch.ones(T, T, dtype=torch.bool))
        scores = scores.masked_fill(~mask, -1e30)
        attn = torch.softmax(scores, dim=-1)
        o = torch.einsum("bhqk,bkhd->bqhd", attn, v).reshape(B, T, E)
        h = h + self.attn.out_proj(o)
        return h + self.mlp.fc2(
            tnn.functional.gelu(self.mlp.fc1(self.ln2(h)))
        )


class _TorchLM(tnn.Module):
    """The reference layout ``convert_transformer_state_dict`` documents:
    plain Linears (explicit math), learned positions, TIED lm head."""

    def __init__(self, V, E, H, L, T):
        super().__init__()
        self.embed = tnn.Embedding(V, E)
        self.pos = tnn.Embedding(T, E)
        self.blocks = tnn.ModuleList(_TorchBlock(E, H, 4 * E) for _ in range(L))
        self.ln_f = tnn.LayerNorm(E)

    def forward(self, tokens):
        T = tokens.shape[1]
        h = self.embed(tokens) + self.pos.weight[:T]
        for blk in self.blocks:
            h = blk(h)
        return self.ln_f(h) @ self.embed.weight.T


def test_imported_transformer_reproduces_torch_logits(model, params):
    torch.manual_seed(0)
    ref = _TorchLM(V, E, H, L, T).eval()
    imported = convert_transformer_state_dict(ref.state_dict(), params)
    rng = np.random.RandomState(5)
    toks = np.asarray(rng.randint(0, V, size=(2, 9)), np.int64)
    with torch.no_grad():
        want = ref(torch.from_numpy(toks)).numpy()
    got, _ = model.apply(imported, (), jnp.asarray(toks, jnp.int32), CTX)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_import_rejects_untied_head(model, params):
    torch.manual_seed(1)
    ref = _TorchLM(V, E, H, L, T)
    sd = dict(ref.state_dict())
    sd["head.weight"] = torch.zeros(V, E)  # a separate (untied) head
    with pytest.raises(ValueError, match="does not consume"):
        convert_transformer_state_dict(sd, params)


def test_import_rejects_wrong_geometry(model, params):
    torch.manual_seed(2)
    ref = _TorchLM(V, E * 2, H, L, T)
    with pytest.raises(ValueError):
        convert_transformer_state_dict(ref.state_dict(), params)
