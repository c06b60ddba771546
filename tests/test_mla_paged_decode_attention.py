"""The absorbed-MLA page-walk decode kernel in interpreter mode against
``jax.numpy``: ragged live pages, an idle row, a speculative width; its
gate's named reasons."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.mla_paged_decode_attention import (
    KERNEL_NAME, mla_paged_decode_attention,
    mla_paged_decode_attention_refusal)

P, MP, N, H, W, L = 16, 4, 24, 4, 48, 32


def plain(q, pages, tables, positions, latent, scale):
    """Gather every row's whole table and attend by a dense softmax."""
    R, S = q.shape[:2]
    ctx = pages[tables].reshape(R, MP * P, W).astype(jnp.float32)
    s = jnp.einsum("rshw,rcw->rshc", q.astype(jnp.float32), ctx) * scale
    seen = jnp.arange(MP * P)[None, None, None, :] <= \
        (positions[:, None] + jnp.arange(S)[None, :])[:, :, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("rshc,rcl->rshl", p, ctx[..., :latent])


def case(R, S, positions, n_tok, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(R, S, H, W)), jnp.float32)
    pages = jnp.asarray(rng.normal(size=(N, P, W)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(N - 1)[:R * MP].reshape(R, MP) + 1, jnp.int32)
    return (q, pages, tables, jnp.asarray(positions, jnp.int32),
            jnp.asarray(n_tok, jnp.int32))


@pytest.mark.parametrize("S,positions,n_tok", [
    (1, [0, P - 1, P, 3 * P + 5, 7], [1, 1, 1, 1, 0]),
    (3, [2 * P - 2, 5, 40, 0, 9], [3, 1, 2, 0, 3]),
], ids=["one-token", "speculative"])
def test_kernel_agrees_with_a_dense_softmax(S, positions, n_tok):
    """Rows of 1 to 4 live pages, a row whose queries straddle a page
    boundary, an idle row: a valid query's output is the dense softmax's,
    an idle row's is zero."""
    q, pages, tables, pos, nt = case(len(positions), S, positions, n_tok)
    assert mla_paged_decode_attention_refusal(
        q.shape, pages.shape, tables.shape, q.dtype, L,
        interpret=True) is None
    got = mla_paged_decode_attention(q, pages, tables, pos, nt, latent=L,
                                     scale=0.2, interpret=True)
    want = plain(q, pages, tables, pos, L, 0.2)
    assert got.shape == (len(positions), S, H, L)
    for r, n in enumerate(n_tok):
        if n == 0:
            assert not np.asarray(got[r]).any()
        np.testing.assert_allclose(got[r, :n], want[r, :n], atol=2e-5)


def test_the_kernel_is_named_for_the_trace():
    q, pages, tables, pos, nt = case(2, 1, [3, 20], [1, 1])
    text = str(jax.make_jaxpr(lambda *a: mla_paged_decode_attention(
        *a, latent=L, scale=1.0, interpret=False))(q, pages, tables, pos,
                                                    nt))
    assert KERNEL_NAME == "mla_paged_decode_attention" and KERNEL_NAME in text


@pytest.mark.parametrize("q,arena,tables,latent,interpret,reason", [
    ((2, 1, 4), (N, P, W), (2, MP), L, True, "rank"),
    ((2, 1, 4, W), (N, P, W + 8), (2, MP), L, True, "shape"),
    ((2, 1, 4, W), (N, P, W), (3, MP), L, True, "shape"),
    ((2, 1, 4, 576), (N, 128, 576), (2, MP), 512, False, "latent_width"),
    ((2, 1, 4, 640), (N, 128, 640), (2, MP), 512, False, None),
    ((2, 1, 4, W), (N, 12, W), (2, MP), L, True, "page_rows"),
    ((512, 1, 4, W), (N, P, W), (512, 64), L, True, "table_size"),
    ((2, 16, 128, 640), (N, 128, 640), (2, MP), 512, False, "vmem"),
])
def test_the_gate_names_its_reason(q, arena, tables, latent, interpret,
                                   reason):
    assert mla_paged_decode_attention_refusal(
        q, arena, tables, jnp.bfloat16 if not interpret else jnp.float32,
        latent, interpret=interpret) == reason
