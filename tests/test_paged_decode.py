"""Paged flash-decode kernel: page-table-walking attention parity.

Three implementations of paged single-token decode must agree:

* contiguous dense rows (the ground-truth layout),
* the XLA gather fallback (``decode_impl="gather"`` — bitwise vs contiguous,
  including with the position-masked page table of ``gather_pages``),
* the Pallas kernel (``decode_impl="pallas"``, interpret mode on CPU —
  within fp32 online-softmax tolerance of both).

Coverage deliberately includes positions straddling page boundaries (the
first row of a fresh page, the last row of a full one) and freed slots whose
page-table rows point at scratch page 0.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.kernels import ops as kops
from repro.kernels.ref import paged_decode_ref
from repro.models import LM
from repro.serve import Request, ServeEngine

TOL = dict(rtol=2e-5, atol=2e-5)


def small_lm(name="llama3.2-3b", layers=2):
    cfg = dataclasses.replace(CONFIGS[name].reduced(), dtype="float32",
                              num_layers=layers)
    lm = LM(cfg)
    return cfg, lm, lm.init(jax.random.key(0))


# ------------------------------------------------------------ kernel-level ----

def test_kernel_matches_ref_random_pools_boundary_positions():
    """Direct kernel-vs-oracle sweep.  Positions cover page boundaries on
    both sides: 0 (single row), page-1 (full first page), page (first row of
    the second page), and the last valid row."""
    rng = np.random.default_rng(0)
    B, KV, G, D, page, M = 6, 2, 3, 16, 4, 3
    P = B * M + 1
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, P, (B, M)), jnp.int32)
    pos = jnp.asarray([0, page - 1, page, 2 * page - 1, 2 * page,
                       M * page - 1], jnp.int32)
    o = kops.paged_decode_attention(q, kp, vp, pt, pos)
    o_ref = paged_decode_ref(q[:, 0], kp, vp, pt, pos)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(o_ref), **TOL)


def test_kernel_dead_pages_do_not_contribute():
    """Table entries past a slot's position may be stale (recycled pages of
    another request) — the walk's early exit must never read them into the
    softmax.  Poison the dead entries with huge values and check the output
    is untouched."""
    rng = np.random.default_rng(1)
    B, KV, G, D, page, M = 2, 1, 2, 8, 4, 4
    P = 8
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    kp = kp.at[7].set(1e9)        # poison page: huge K would dominate softmax
    vp = vp.at[7].set(jnp.nan)    # ... and NaN V would propagate instantly
    pos = jnp.asarray([2, 5], jnp.int32)   # slots use pages 0..0 and 0..1
    live = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    dead = jnp.asarray([[1, 7, 7, 7], [3, 4, 7, 7]], jnp.int32)
    o_live = kops.paged_decode_attention(q, kp, vp, live, pos)
    o_dead = kops.paged_decode_attention(q, kp, vp, dead, pos)
    assert np.isfinite(np.asarray(o_dead)).all()
    np.testing.assert_allclose(np.asarray(o_dead), np.asarray(o_live), **TOL)


def test_kernel_fuzz_random_shapes_three_way():
    """Seeded fuzz over (B, M, page_size, positions): pallas-interpret vs
    the XLA gather path vs the dense ``paged_decode_ref`` oracle must agree
    at every draw.  Positions deliberately include pos=0, both sides of
    every page boundary, and the last valid row; tables include repeated
    physical pages (prefix sharing aliases pages across slots)."""
    from repro.models.attention import decode_attention

    rng = np.random.default_rng(2024)          # reproducible by seed
    for trial in range(6):
        B = int(rng.integers(1, 6))
        KV = int(rng.integers(1, 3))
        G = int(rng.integers(1, 4))
        D = int(rng.choice([4, 8, 16]))
        page = int(rng.choice([2, 4, 8]))
        M = int(rng.integers(1, 5))
        P = int(B * M + rng.integers(1, 4))
        q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
        # repeated entries alias pages across slots, like prefix sharing
        pt = jnp.asarray(rng.integers(1, P, (B, M)), jnp.int32)
        boundary = np.array([0, page - 1, page, M * page - 1])
        pos = np.where(rng.random(B) < 0.5,
                       rng.choice(boundary, B),
                       rng.integers(0, M * page, B)).astype(np.int32)
        pos = jnp.asarray(np.minimum(pos, M * page - 1))

        o_ref = paged_decode_ref(q[:, 0], kp, vp, pt, pos)
        o_kernel = kops.paged_decode_attention(q, kp, vp, pt, pos)
        o_gather = decode_attention(q, kp, vp, pos, page_table=pt,
                                    impl="gather")
        ctx = dict(trial=trial, B=B, KV=KV, G=G, D=D, page=page, M=M,
                   pos=np.asarray(pos).tolist())
        np.testing.assert_allclose(np.asarray(o_kernel[:, 0]),
                                   np.asarray(o_ref), err_msg=str(ctx),
                                   **TOL)
        np.testing.assert_allclose(np.asarray(o_gather[:, 0]),
                                   np.asarray(o_ref), err_msg=str(ctx),
                                   **TOL)


def test_kernel_quantized_matches_ref_and_float_pool():
    """Int8 pools with per-row scales: the kernel's in-register dequant must
    match the dequantizing gather oracle within kernel tolerance, and both
    must track the original float pool within the quantization error bound.
    Positions cover the same page boundaries as the float sweep."""
    from repro.kernels.quant import quantize_kv

    rng = np.random.default_rng(5)
    B, KV, G, D, page, M = 6, 2, 3, 16, 4, 3
    P = B * M + 1
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    assert ks.shape == (P, page, KV)
    pt = jnp.asarray(rng.integers(1, P, (B, M)), jnp.int32)
    pos = jnp.asarray([0, page - 1, page, 2 * page - 1, 2 * page,
                       M * page - 1], jnp.int32)
    o = kops.paged_decode_attention(q, kq, vq, pt, pos, k_scale=ks,
                                    v_scale=vs)
    o_ref = paged_decode_ref(q[:, 0], kq, vq, pt, pos, k_scale=ks,
                             v_scale=vs)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(o_ref), **TOL)
    # and the quantized result stays near the float-pool softmax: per-element
    # K/V error is <= absmax/254, attention smooths it well under 2%
    o_float = paged_decode_ref(q[:, 0], kp, vp, pt, pos)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(o_float),
                               rtol=0.05, atol=0.02)


def test_kernel_quantized_dead_pages_and_scratch():
    """Poisoned dead pages — including poisoned *scales* — must not leak
    into a quantized walk, and scratch-routed slots stay finite."""
    from repro.kernels.quant import quantize_kv

    rng = np.random.default_rng(6)
    B, KV, G, D, page, M, P = 2, 1, 2, 8, 4, 4, 8
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    kq, ks = quantize_kv(jnp.asarray(rng.normal(size=(P, page, KV, D)),
                                     jnp.float32))
    vq, vs = quantize_kv(jnp.asarray(rng.normal(size=(P, page, KV, D)),
                                     jnp.float32))
    ks = ks.at[7].set(1e30)                  # poisoned scale on a dead page
    vs = vs.at[7].set(jnp.nan)
    pos = jnp.asarray([2, 5], jnp.int32)
    live = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    dead = jnp.asarray([[1, 7, 7, 7], [3, 4, 7, 7]], jnp.int32)
    o_live = kops.paged_decode_attention(q, kq, vq, live, pos, k_scale=ks,
                                         v_scale=vs)
    o_dead = kops.paged_decode_attention(q, kq, vq, dead, pos, k_scale=ks,
                                         v_scale=vs)
    assert np.isfinite(np.asarray(o_dead)).all()
    np.testing.assert_allclose(np.asarray(o_dead), np.asarray(o_live), **TOL)


def test_quantized_partials_kernel_vs_gather():
    """Sharded int8 building block: the kernel's partial triple over a local
    pool shard (with local scale shards) matches the gather partials, and
    the two-chip merge reconstructs the full quantized softmax."""
    from repro.kernels.quant import quantize_kv
    from repro.models.attention import (decode_attention,
                                        paged_gather_partials)

    rng = np.random.default_rng(9)
    B, KV, G, D, page, M, P = 3, 2, 2, 8, 4, 3, 12
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    kq, ks = quantize_kv(jnp.asarray(rng.normal(size=(P, page, KV, D)),
                                     jnp.float32))
    vq, vs = quantize_kv(jnp.asarray(rng.normal(size=(P, page, KV, D)),
                                     jnp.float32))
    pt = jnp.asarray(rng.integers(1, P, (B, M)), jnp.int32)
    pos = jnp.asarray([0, 5, 11], jnp.int32)
    half = P // 2

    def window(c):
        s = slice(c * half, (c + 1) * half)
        return kq[s], vq[s], ks[s], vs[s], jnp.int32(c * half)

    parts = []
    for c in range(2):
        kw, vw, ksw, vsw, off = window(c)
        g = paged_gather_partials(q, kw, vw, pt, pos, off, k_scale=ksw,
                                  v_scale=vsw)
        k = kops.paged_decode_partials(q, kw, vw, pt, pos, off, k_scale=ksw,
                                       v_scale=vsw)
        np.testing.assert_allclose(np.asarray(k[1]), np.asarray(g[1]), **TOL)
        np.testing.assert_allclose(np.asarray(k[2]), np.asarray(g[2]), **TOL)
        np.testing.assert_allclose(np.asarray(k[0]), np.asarray(g[0]),
                                   rtol=2e-4, atol=2e-4)
        parts.append(g)
    ms = jnp.stack([m for _, _, m in parts])
    gm = ms.max(axis=0)
    num = sum(acc * jnp.exp(m - gm)[:, None, :, :, None]
              for acc, _, m in parts)
    den = sum(l * jnp.exp(m - gm) for _, l, m in parts)
    merged = num / jnp.maximum(den, 1e-30)[:, None, :, :, None]
    full = decode_attention(q, kq, vq, pos, page_table=pt, impl="gather",
                            k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full), **TOL)


def test_partials_merge_matches_full_softmax_singlehost():
    """The sharded path's building blocks, checked without a mesh: gather
    partials over two half-pools, merged with the partial-softmax formula,
    equal the full-pool softmax — and the pallas partials triple matches the
    gather partials triple on the same half-pool."""
    from repro.models.attention import (decode_attention,
                                        paged_gather_partials)

    rng = np.random.default_rng(8)
    B, KV, G, D, page, M, P = 3, 2, 2, 8, 4, 3, 12   # halves of 6 pages
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, P, (B, M)), jnp.int32)
    pos = jnp.asarray([0, 5, 11], jnp.int32)

    half = P // 2
    parts = [paged_gather_partials(q, kp[c * half:(c + 1) * half],
                                   vp[c * half:(c + 1) * half], pt, pos,
                                   jnp.int32(c * half)) for c in range(2)]
    # host-side merge (the on-mesh version uses pmax/psum over chips)
    ms = jnp.stack([m for _, _, m in parts])
    gm = ms.max(axis=0)
    num = sum(acc * jnp.exp(m - gm)[:, None, :, :, None]
              for acc, _, m in parts)
    den = sum(l * jnp.exp(m - gm) for _, l, m in parts)
    merged = num / jnp.maximum(den, 1e-30)[:, None, :, :, None]
    full = decode_attention(q, kp, vp, pos, page_table=pt, impl="gather")
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full), **TOL)

    # kernel partials == gather partials on one half-pool window
    acc_g, l_g, m_g = parts[1]
    acc_k, l_k, m_k = kops.paged_decode_partials(
        q, kp[half:], vp[half:], pt, pos, jnp.int32(half))
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_g), **TOL)
    # only compare running maxima where a live page exists (both report
    # NEG_INF identity otherwise, but -1e30 equality is exact anyway)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_g), **TOL)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_g),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------- decode parity ----

def test_ragged_8slot_kernel_vs_gather_vs_contiguous():
    """The acceptance workload: eight slots at eight depths (several
    straddling the page-size-8 boundary).  Gather stays bitwise vs
    contiguous; the kernel matches within fp32 online-softmax tolerance —
    through two chained decode steps so the kernel also consumes
    scatter-written pages."""
    cfg, lm, params = small_lm()
    B, S, pg = 8, 32, 8
    rng = np.random.default_rng(7)
    lens = [3, 11, 7, 1, 14, 5, 9, 2]     # 7->8 and 11->12 cross page rows
    contig = lm.init_cache(B, S, dtype=jnp.float32, backend="contiguous")
    paged = lm.init_cache(B, S, dtype=jnp.float32, backend="paged",
                          page_size=pg)
    for b, plen in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        assert contig.alloc(b, plen + 4) == 0
        assert paged.alloc(b, plen + 4, prefix=prompt) == 0
        _, _, pc = lm.forward(params, {"tokens": jnp.asarray(prompt[None])},
                              collect_cache=True)
        contig.write_prefill(b, pc["layers"])
        paged.write_prefill(b, pc["layers"])
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray(np.array(lens, np.int32))

    lc, cc = lm.decode_step(params, toks, contig.decode_view(), pos)
    lg, cg = lm.decode_step(params, toks, paged.decode_view(), pos)
    lk, ck = lm.decode_step(params, toks, paged.decode_view(), pos,
                            decode_impl="pallas")
    np.testing.assert_array_equal(np.asarray(lc), np.asarray(lg))
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lg), **TOL)
    # the kernel path's cache writes land in the same pages/rows; values
    # beyond layer 0 inherit the attention tolerance (layer N's K/V project
    # layer N-1's output), so this is allclose, not bitwise
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), **TOL), cg["layers"], ck["layers"])
    # step 2: positions advance across more page boundaries
    contig.update(cc)
    paged.update(cg)
    lc2, _ = lm.decode_step(params, toks, contig.decode_view(), pos + 1)
    lg2, _ = lm.decode_step(params, toks, paged.decode_view(), pos + 1)
    lk2, _ = lm.decode_step(params, toks, paged.decode_view(), pos + 1,
                            decode_impl="pallas")
    np.testing.assert_array_equal(np.asarray(lc2), np.asarray(lg2))
    np.testing.assert_allclose(np.asarray(lk2), np.asarray(lg2), **TOL)


def test_freed_slot_scratch_page_rows_are_inert():
    """A freed slot's page-table row is all scratch-page zeros and the
    engine decodes it at position 0: the kernel must return finite garbage
    for that slot while active slots' logits are unperturbed."""
    cfg, lm, params = small_lm()
    B, S, pg = 4, 16, 4
    rng = np.random.default_rng(3)
    paged = lm.init_cache(B, S, dtype=jnp.float32, backend="paged",
                          page_size=pg)
    lens = [5, 6, 4]
    for b, plen in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        assert paged.alloc(b, plen + 2, prefix=prompt) == 0
        _, _, pc = lm.forward(params, {"tokens": jnp.asarray(prompt[None])},
                              collect_cache=True)
        paged.write_prefill(b, pc["layers"])
    paged.free(1)                              # slot 1 -> scratch page 0
    assert np.all(paged.page_table[1] == 0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray([5, 0, 4, 0], jnp.int32)   # freed/empty slots at 0
    lg, _ = lm.decode_step(params, toks, paged.decode_view(), pos)
    lk, _ = lm.decode_step(params, toks, paged.decode_view(), pos,
                           decode_impl="pallas")
    assert np.isfinite(np.asarray(lk)).all()
    for b in (0, 2):                           # live slots: full parity
        np.testing.assert_allclose(np.asarray(lk[b]), np.asarray(lg[b]),
                                   **TOL)


def test_decode_impl_rejected_values():
    cfg, lm, params = small_lm()
    with pytest.raises(AssertionError):
        lm.init_cache(2, 16, dtype=jnp.float32, backend="paged",
                      decode_impl="typo")
    paged = lm.init_cache(2, 16, dtype=jnp.float32, backend="paged",
                          page_size=4)
    toks = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(AssertionError):
        lm.decode_step(params, toks, paged.decode_view(),
                       jnp.zeros(2, jnp.int32), decode_impl="typo")


# ---------------------------------------------------------- clamped gather ----

# distinct extents, so a (B, M, page, ...) shape names only the gathered
# view (and its int8 scales) in the lowered module
GB, GM, GPAGE, GKV, GG, GD, GP, GC = 3, 5, 4, 2, 3, 16, 11, 6


def _gather_case(int8):
    from repro.kernels.quant import quantize_kv
    rng = np.random.default_rng(11)
    kp, vp = (jnp.asarray(rng.normal(size=(GP, GPAGE, GKV, GD)), jnp.float32)
              for _ in range(2))
    ks = vs = None
    if int8:
        kp, ks = quantize_kv(kp)
        vp, vs = quantize_kv(vp)
    q = jnp.asarray(rng.normal(size=(GB, GC, GKV, GG, GD)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, GP, (GB, GM)), jnp.int32)
    pos = jnp.asarray([0, 7, GM * GPAGE - 1], jnp.int32)
    return q, kp, vp, ks, vs, pt, pos


def _site(name):
    from repro.models import attention as attn
    off = jnp.int32(0)
    qpos = lambda pos: pos[:, None] - jnp.arange(GC)[::-1][None, :]
    return {
        "gather_pages": lambda q, kp, vp, ks, vs, pt, pos:
            attn.gather_pages(kp, pt, pos),
        "decode_attention": lambda q, kp, vp, ks, vs, pt, pos:
            attn.decode_attention(q[:, :1], kp, vp, pos, page_table=pt,
                                  impl="gather", k_scale=ks, v_scale=vs),
        "paged_gather_partials": lambda q, kp, vp, ks, vs, pt, pos:
            attn.paged_gather_partials(q[:, :1], kp, vp, pt, pos, off,
                                       k_scale=ks, v_scale=vs),
        "paged_gather_chunk_partials": lambda q, kp, vp, ks, vs, pt, pos:
            attn.paged_gather_chunk_partials(q, kp, vp, pt, qpos(pos), pos,
                                             off, k_scale=ks, v_scale=vs),
    }[name]


@pytest.mark.parametrize("site,int8", [
    ("gather_pages", False), ("decode_attention", False),
    ("decode_attention", True), ("paged_gather_partials", False),
    ("paged_gather_partials", True), ("paged_gather_chunk_partials", False),
    ("paged_gather_chunk_partials", True)])
def test_paged_gather_lowers_without_gathered_view_select(site, int8):
    """Every pool and scale gather on the paged read path states its ids
    are in range: the module holds the bare gather of the (B, M, page, ...)
    view and no select of that shape (``jnp.take``'s default fill mode
    re-writes the whole view through a mask and a NaN select).  The int32
    (B, M) live-page ``where`` on the table is not such a select."""
    import re
    args = _gather_case(int8)
    text = jax.jit(_site(site)).lower(*args).as_text()
    view = re.compile(rf"tensor<{GB}x{GM}x{GPAGE}x[0-9x]*\w+>")
    gathers = [ln for ln in text.splitlines()
               if "stablehlo.gather" in ln and view.search(ln)]
    assert gathers, text
    if int8 and site != "gather_pages":
        assert any(f"{GB}x{GM}x{GPAGE}x{GKV}xf32>" in ln
                   for ln in gathers), text     # the scales' gather too
    selects = [ln.strip() for ln in text.splitlines()
               if "stablehlo.select" in ln and view.search(ln)]
    assert not selects, selects


@pytest.mark.parametrize("seed,chips", [(0, None), (1, 2), (2, None),
                                        (3, 2)])
def test_page_table_ids_stay_in_range_and_gather_is_unclamped(seed, chips):
    """Seeded op streams over ``PagedCache`` — whole and chunked admissions
    with prefix hits, extends, shields, frees and preemptive evictions:
    after every op each table entry (host table and decode view) lies in
    ``[0, num_pages)``, the assumption the clamped gather rests on.  On
    those tables ``gather_pages`` (dead-page redirect on and off) is
    bitwise the fill-mode ``jnp.take``, so the clamp never changed an id."""
    from repro.models.attention import gather_pages
    from repro.serve.kvcache import PagedCache

    rng = np.random.default_rng(seed)
    cfg = dataclasses.replace(CONFIGS["llama3.2-3b"].reduced(),
                              dtype="float32", num_layers=1)
    B, S, page = 4, 24, 4
    kv = PagedCache(LM(cfg), B, S, dtype=jnp.float32, page_size=page,
                    num_pages=14, locality_chips=chips)
    # two slots' prompts repeat, so admissions hit shared prefix pages
    prompts = [np.arange(16, dtype=np.int32), np.arange(16, dtype=np.int32),
               np.arange(16, dtype=np.int32) + 3]
    pool = jnp.asarray(rng.normal(size=(kv.P, page, 2, 8)), jnp.float32)
    chunked = {}                        # slot -> [covered, length, prompt]
    ops = ["alloc", "alloc_chunked", "extend", "extend", "free", "evict"]
    seen = set()

    def check():
        for tbl in (kv.page_table, np.asarray(kv.decode_view()["page_table"])):
            assert tbl.dtype == np.int32
            assert 0 <= tbl.min() and tbl.max() < kv.P, tbl
        pt = jnp.asarray(kv.page_table)
        pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
        live = jnp.arange(kv.max_pages)[None, :] <= pos[:, None] // page
        fill = jnp.take(pool, jnp.where(live, pt, 0), axis=0, mode="fill")
        np.testing.assert_array_equal(
            np.asarray(gather_pages(pool, pt, pos)),
            np.asarray(fill.reshape(B, -1, 2, 8)))
        np.testing.assert_array_equal(
            np.asarray(gather_pages(pool, pt)),
            np.asarray(jnp.take(pool, pt, axis=0, mode="fill")
                       .reshape(B, -1, 2, 8)))

    for _ in range(50):
        op, slot = ops[int(rng.integers(len(ops)))], int(rng.integers(B))
        busy = bool(kv._slot_pages[slot])
        prompt = prompts[int(rng.integers(len(prompts)))]
        length = int(rng.integers(2, S + 1))
        if op == "alloc" and not busy:
            got = kv.alloc(slot, length, prefix=prompt[:length])
            if got is not None:
                seen.update(["alloc", "shared"] if got else ["alloc"])
        elif op == "alloc_chunked" and not busy:
            first = min(page, length)
            if kv.alloc_chunked(slot, length, first,
                                prefix=prompt[:length]) is not None:
                kv.set_decode_shield(slot, True)
                kv.register_landed(slot, prompt[:length], first)
                chunked[slot] = [first, length, prompt[:length]]
                seen.add("alloc_chunked")
        elif op == "extend" and slot in chunked:
            cov, total, pr = chunked[slot]
            cover = min(cov + page, total)
            if kv.extend(slot, cover):
                kv.register_landed(slot, pr, cover)
                chunked[slot][0] = cover
                seen.add("extend")
                if cover == total:
                    kv.set_decode_shield(slot, False)
                    del chunked[slot]
        elif op in ("free", "evict") and busy:
            (kv.free if op == "free" else kv.evict)(slot)
            chunked.pop(slot, None)
            seen.add(op)
        check()
    kv.verify()
    assert {"alloc", "shared", "alloc_chunked", "extend", "free",
            "evict"} <= seen, seen
