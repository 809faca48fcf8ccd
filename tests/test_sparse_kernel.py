"""ops/sparse.py kernel tests: sorted-merge top-k vs numpy oracle,
including chunk splitting and msm/AND counting."""

import numpy as np
import pytest

import jax.numpy as jnp

from elasticsearch_tpu.ops import sparse


def brute_force(rows, flat_docs, flat_impact, d_pad, min_count):
    """rows: [(start, ln, w, tid)...] per query; dense accumulate."""
    out = []
    for row, mc in zip(rows, min_count):
        score = np.zeros(d_pad, dtype=np.float64)
        cnt = np.zeros(d_pad, dtype=np.int64)
        for (s, ln, w, _tid) in row:
            d = flat_docs[s:s + ln]
            imp = flat_impact[s:s + ln]
            score[d] += w * imp
            cnt[d] += 1
        ok = (score > 0) & (cnt >= mc)
        out.append([(int(d), float(score[d]))
                    for d in np.nonzero(ok)[0]])
    return out


def make_flat(rng, n_terms, d_pad, max_df, slack=4352):
    # slack must cover the kernel's max_len bucket (≤ chunk_cap = 4096):
    # sorted_merge_topk slices max_len lanes from each start via
    # dynamic_slice, which CLAMPS out-of-bounds starts — too little tail
    # padding silently shifts the last term's read window onto earlier
    # postings. The serving planner always pads flats by the chunk cap.
    rows = []
    sizes = [int(rng.integers(1, max_df)) for _ in range(n_terms)]
    total = sum(sizes)
    flat_docs = np.full(total + slack, d_pad, dtype=np.int32)
    flat_imp = np.zeros(total + slack, dtype=np.float32)
    pos = 0
    extents = []
    for sz in sizes:
        docs = np.sort(rng.choice(d_pad, size=sz, replace=False)).astype(np.int32)
        flat_docs[pos:pos + sz] = docs
        flat_imp[pos:pos + sz] = rng.uniform(0.1, 1.0, size=sz).astype(np.float32)
        extents.append((pos, sz))
        pos += sz
    return flat_docs, flat_imp, extents


def row_starts_of(ext, flat_len):
    """make_flat extents (contiguous) → row_starts int64[n_terms+1]."""
    rs = [pos for pos, _ in ext] + [ext[-1][0] + ext[-1][1]]
    return np.asarray(rs, dtype=np.int64)


def compressed_operands(flat_docs, flat_imp, ext, d_pad, plan):
    """Compress the test corpus and derive the per-slot operands the
    compressed variants need (mirrors prepare_query_batch). When the
    doc stream passes the per-block delta gate the operands switch to
    the u8 delta format, exactly as device residency does — so small
    d_pad corpora route the parity sweeps through the delta decode."""
    rs = row_starts_of(ext, flat_docs.size)
    reason = sparse.compress_reason(flat_docs, flat_imp, rs, d_pad)
    assert reason is None, reason
    docs16, code16, rank16, block_max, res_vals, res_rs = \
        sparse.compress_flat(flat_docs, flat_imp, rs, d_pad)
    rr = (np.searchsorted(rs, plan.starts, side="right") - 1).astype(
        np.int32)
    rr = np.clip(rr, 0, len(ext) - 1)
    res_starts = res_rs[rr].astype(np.int32)
    res_lens = (res_rs[rr + 1] - res_rs[rr]).astype(np.int32)
    res_lens[plan.lengths == 0] = 0
    blk = (plan.starts // sparse.COMPRESSED_BLOCK).astype(np.int32)
    extra = dict(flat_rank=jnp.asarray(rank16),
                 res_starts=jnp.asarray(res_starts),
                 res_lens=jnp.asarray(res_lens),
                 res_vals=jnp.asarray(res_vals),
                 block_max=jnp.asarray(block_max),
                 blk_starts=jnp.asarray(blk),
                 slot_terms=jnp.asarray(rr))
    doc_stream = docs16
    if sparse.delta_doc_reason(flat_docs, rs) is None:
        nbd = (flat_docs.size + sparse.COMPRESSED_BLOCK - 1) \
            // sparse.COMPRESSED_BLOCK + 2
        docs8, bases = sparse.delta_encode_docs(flat_docs, rs, nbd)
        extra.update(
            doc_bases=jnp.asarray(bases),
            dbs_starts=jnp.asarray(
                (plan.starts // sparse.COMPRESSED_BLOCK).astype(np.int32)),
            dlo_starts=jnp.asarray(
                (plan.starts % sparse.COMPRESSED_BLOCK).astype(np.int32)))
        doc_stream = docs8
    return (doc_stream, code16, extra)


def run_kernel(flat_docs, flat_imp, rows, mins, d_pad, k, chunk_cap=4096,
               with_counts=False, with_totals=False, variant="ref",
               ext=None):
    plan = sparse.plan_slots(rows, mins, chunk_cap=chunk_cap, lane=8)
    extra = {}
    if variant in sparse.COMPRESSED_VARIANTS:
        assert ext is not None, "compressed run needs the term extents"
        flat_docs, flat_imp, extra = compressed_operands(
            flat_docs, flat_imp, ext, d_pad, plan)
    out = sparse.sorted_merge_topk(
        jnp.asarray(flat_docs), jnp.asarray(flat_imp),
        jnp.asarray(plan.starts), jnp.asarray(plan.lengths),
        jnp.asarray(plan.weights), jnp.asarray(plan.min_count),
        max_len=plan.max_len, d_pad=d_pad, k=k,
        t_window=plan.window, with_counts=with_counts,
        with_totals=with_totals, variant=variant, **extra)
    if with_totals:
        vals, docs, totals = out
        return np.asarray(vals), np.asarray(docs), np.asarray(totals)
    vals, docs = out
    return np.asarray(vals), np.asarray(docs)


class TestSortedMergeTopk:
    def test_or_query_matches_oracle(self, seeded_np):
        d_pad = 512
        flat_docs, flat_imp, ext = make_flat(seeded_np, 6, d_pad, 200)
        weights = [1.7, 0.9, 2.3, 0.5, 1.1, 3.0]
        rows = [[(ext[t][0], ext[t][1], weights[t], t) for t in (0, 2, 4)],
                [(ext[t][0], ext[t][1], weights[t], t) for t in (1, 3)],
                [(ext[5][0], ext[5][1], weights[5], 5)]]
        mins = [1, 1, 1]
        vals, docs = run_kernel(flat_docs, flat_imp, rows, mins, d_pad, k=600)
        expected = brute_force(rows, flat_docs, flat_imp, d_pad, mins)
        for qi, exp in enumerate(expected):
            exp_sorted = sorted(exp, key=lambda t: (-t[1], t[0]))
            got = [(int(d), float(v)) for v, d in zip(vals[qi], docs[qi])
                   if v != float("-inf")]
            assert len(got) == len(exp_sorted)
            for (gd, gv), (ed, ev) in zip(got, exp_sorted):
                assert gd == ed
                assert gv == pytest.approx(ev, rel=1e-5)

    def test_chunking_preserves_scores(self, seeded_np):
        """Tiny chunk_cap forces every row to split into many slots; result
        must be identical to the unchunked run."""
        d_pad = 256
        flat_docs, flat_imp, ext = make_flat(seeded_np, 4, d_pad, 180)
        rows = [[(ext[t][0], ext[t][1], 1.0 + t, t) for t in range(4)]]
        v1, d1 = run_kernel(flat_docs, flat_imp, rows, [1], d_pad, k=300,
                            chunk_cap=4096)
        v2, d2 = run_kernel(flat_docs, flat_imp, rows, [1], d_pad, k=300,
                            chunk_cap=16)
        m1 = v1[0] != float("-inf")
        m2 = v2[0] != float("-inf")
        assert m1.sum() == m2.sum()
        np.testing.assert_array_equal(d1[0][m1], d2[0][m2])
        np.testing.assert_allclose(v1[0][m1], v2[0][m2], rtol=1e-5)

    def test_and_semantics(self, seeded_np):
        d_pad = 256
        flat_docs, flat_imp, ext = make_flat(seeded_np, 3, d_pad, 120)
        rows = [[(ext[t][0], ext[t][1], 1.0, t) for t in range(3)]]
        mins = [3]  # AND of 3 terms
        vals, docs = run_kernel(flat_docs, flat_imp, rows, mins, d_pad,
                                k=256, with_counts=True)
        expected = brute_force(rows, flat_docs, flat_imp, d_pad, mins)[0]
        got = {int(d) for v, d in zip(vals[0], docs[0]) if v != float("-inf")}
        assert got == {d for d, _ in expected}

    def test_and_semantics_with_chunking(self, seeded_np):
        d_pad = 256
        flat_docs, flat_imp, ext = make_flat(seeded_np, 3, d_pad, 120)
        rows = [[(ext[t][0], ext[t][1], 1.0, t) for t in range(3)]]
        mins = [2]  # at least 2 of 3
        v1, d1 = run_kernel(flat_docs, flat_imp, rows, mins, d_pad, k=256,
                            with_counts=True, chunk_cap=16)
        expected = brute_force(rows, flat_docs, flat_imp, d_pad, mins)[0]
        got = {int(d) for v, d in zip(v1[0], d1[0]) if v != float("-inf")}
        assert got == {d for d, _ in expected}

    def test_absent_term_zero_length_slot(self, seeded_np):
        d_pad = 128
        flat_docs, flat_imp, ext = make_flat(seeded_np, 2, d_pad, 60)
        # second "term" absent (zero-length row): AND can never match
        rows = [[(ext[0][0], ext[0][1], 1.0, 0), (0, 0, 0.0, 1)]]
        vals, docs = run_kernel(flat_docs, flat_imp, rows, [2], d_pad,
                                k=128, with_counts=True)
        assert (vals[0] == float("-inf")).all()
        # OR still matches term 0's docs
        vals, docs = run_kernel(flat_docs, flat_imp, rows, [1], d_pad,
                                k=128, with_counts=True)
        got = {int(d) for v, d in zip(vals[0], docs[0]) if v != float("-inf")}
        assert got == set(int(x) for x in
                          flat_docs[ext[0][0]:ext[0][0] + ext[0][1]])

    def test_tie_break_smaller_doc_first(self):
        d_pad = 64
        # two docs with identical impact from one term
        flat_docs = np.array([5, 9] + [d_pad] * 32, dtype=np.int32)
        flat_imp = np.array([0.5, 0.5] + [0.0] * 32, dtype=np.float32)
        rows = [[(0, 2, 1.0, 0)]]
        vals, docs = run_kernel(flat_docs, flat_imp, rows, [1], d_pad, k=2)
        assert docs[0][0] == 5 and docs[0][1] == 9


def make_case(rng, *, tie_heavy=False):
    """Random corpus + query rows for a packed-vs-ref parity check.

    tie_heavy quantizes impacts to multiples of 1/8 so many docs land on
    EXACTLY equal scores — the regime where the packed path's tie-break
    (earliest doc id) must still match the reference bit for bit."""
    d_pad = int(rng.integers(200, 5000))
    n_terms = int(rng.integers(2, 7))
    max_df = max(2, min(d_pad - 1, int(rng.integers(20, 800))))
    flat_docs, flat_imp, ext = make_flat(rng, n_terms, d_pad, max_df)
    if tie_heavy:
        flat_imp = (np.ceil(flat_imp * 8.0) / 8.0).astype(np.float32)
    weights = [float(rng.uniform(0.2, 4.0)) for _ in range(n_terms)]
    if tie_heavy:
        weights = [1.0] * n_terms
    rows = [[(ext[t][0], ext[t][1], weights[t], t)
             for t in range(n_terms)]]
    mc = int(rng.integers(1, n_terms + 1))  # OR → msm → AND
    k = int(rng.integers(1, 64))
    return flat_docs, flat_imp, rows, [mc], d_pad, k, ext


def assert_variants_identical(flat_docs, flat_imp, rows, mins, d_pad, k,
                              ext=None, chunk_cap=4096):
    """Bit-identical scores, doc ids, AND totals across variants. With
    `ext` (term extents) the compressed pair joins the comparison —
    the pruning-safety property IS this bitwise equality: a block-max
    skip that dropped a true top-k doc would change docs/scores."""
    wc = any(m > 1 for m in mins)
    rv, rd, rt = run_kernel(flat_docs, flat_imp, rows, mins, d_pad, k,
                            chunk_cap=chunk_cap, with_counts=wc,
                            with_totals=True, variant="ref")
    others = ["packed"]
    if ext is not None:
        others += list(sparse.COMPRESSED_VARIANTS)
    for variant in others:
        pv, pd_, pt = run_kernel(flat_docs, flat_imp, rows, mins, d_pad, k,
                                 chunk_cap=chunk_cap, with_counts=wc,
                                 with_totals=True, variant=variant,
                                 ext=ext)
        # bitwise: view as uint32 so -inf/-0.0 compare exactly too
        np.testing.assert_array_equal(rv.view(np.uint32),
                                      pv.view(np.uint32),
                                      err_msg=variant)
        np.testing.assert_array_equal(rd, pd_, err_msg=variant)
        np.testing.assert_array_equal(rt, pt, err_msg=variant)
    return rv, rd, rt


class TestPackedParity:
    """Packed single-key variant vs reference: the acceptance bar is
    bit-identical scores, doc ids, and totals."""

    def test_parity_small(self, seeded_np):
        # tier-1 sized: a handful of random corpora incl. tie-heavy
        for i in range(4):
            case = make_case(seeded_np, tie_heavy=(i % 2 == 1))
            assert_variants_identical(*case)

    @pytest.mark.slow
    def test_parity_sweep(self, seeded_np):
        # the full sweep: random corpora × msm/AND × tie-heavy × chunking
        for i in range(40):
            fd, fi, rows, mins, d_pad, k, ext = make_case(
                seeded_np, tie_heavy=(i % 3 == 0))
            cap = 64 if i % 4 == 0 else 4096  # force chunk splitting too
            assert_variants_identical(fd, fi, rows, mins, d_pad, k,
                                      ext=ext, chunk_cap=cap)

    @pytest.mark.slow
    def test_parity_near_doc_limit(self, seeded_np):
        # d_pad just under the packed range: codes use the full 16 doc bits
        d_pad = sparse.PACKED_DOC_LIMIT - 1
        flat_docs, flat_imp, ext = make_flat(seeded_np, 3, d_pad, 3000)
        rows = [[(ext[t][0], ext[t][1], 1.0 + t, t) for t in range(3)]]
        assert_variants_identical(flat_docs, flat_imp, rows, [1],
                                  d_pad, 50, ext=ext)

    def test_tie_break_earliest_doc_id(self):
        # many docs at EXACTLY the same score: both variants must emit
        # them in ascending doc-id order
        d_pad = 512
        docs = np.arange(7, 450, 7, dtype=np.int32)
        flat_docs = np.concatenate(
            [docs, np.full(4160, d_pad, dtype=np.int32)])
        flat_imp = np.concatenate(
            [np.full(docs.size, 0.25, dtype=np.float32),
             np.zeros(4160, dtype=np.float32)])
        rows = [[(0, docs.size, 2.0, 0)]]
        rv, rd, _ = assert_variants_identical(
            flat_docs, flat_imp, rows, [1], d_pad, 10,
            ext=[(0, docs.size)])
        np.testing.assert_array_equal(rd[0], docs[:10])

    def test_packed_rejects_doc_overflow(self, seeded_np):
        d_pad = sparse.PACKED_DOC_LIMIT  # one past the packable range
        flat_docs, flat_imp, ext = make_flat(seeded_np, 2, d_pad, 50)
        rows = [[(ext[t][0], ext[t][1], 1.0, t) for t in range(2)]]
        with pytest.raises(ValueError, match="packed"):
            run_kernel(flat_docs, flat_imp, rows, [1], d_pad, 10,
                       variant="packed")
        # ref variant is unaffected by the doc range
        run_kernel(flat_docs, flat_imp, rows, [1], d_pad, 10,
                   variant="ref")

    def test_unknown_variant_rejected(self, seeded_np):
        flat_docs, flat_imp, ext = make_flat(seeded_np, 1, 64, 10)
        rows = [[(ext[0][0], ext[0][1], 1.0, 0)]]
        with pytest.raises(ValueError, match="variant"):
            run_kernel(flat_docs, flat_imp, rows, [1], 64, 4,
                       variant="fancy")

    def test_packable_gates(self):
        # doc-range gate
        assert sparse.packable(sparse.PACKED_DOC_LIMIT - 1)
        assert not sparse.packable(sparse.PACKED_DOC_LIMIT)
        # weight gates: negative, non-finite, and out-of-range magnitudes
        ok = np.array([0.5, 2.0], dtype=np.float32)
        assert sparse.packable(1000, ok)
        assert not sparse.packable(1000, np.array([-1.0, 2.0]))
        assert not sparse.packable(1000, np.array([np.inf, 1.0]))
        assert not sparse.packable(1000, np.array([np.nan, 1.0]))
        assert not sparse.packable(1000, np.array([1e31, 1.0]))
        assert not sparse.packable(1000, np.array([1e-13, 1.0]))
        # zeros are fine (absent-term slots carry weight 0)
        assert sparse.packable(1000, np.array([0.0, 1.0]))

    def test_code16_monotone_lower_bound(self):
        x = jnp.asarray(np.geomspace(1e-12, 1e30, 400, dtype=np.float32))
        codes = np.asarray(sparse.impact_code16(x))
        assert (np.diff(codes.astype(np.int64)) >= 0).all()
        dec = np.asarray(sparse.decode_code16(jnp.asarray(codes)))
        xs = np.asarray(x)
        assert (dec <= xs).all()            # lower bound
        assert (codes > 0).all()            # never rounds to "no match"


class TestTotals:
    @pytest.mark.parametrize("variant", sparse.KERNEL_VARIANTS)
    def test_totals_exceed_k_both_variants(self, seeded_np, variant):
        """TotalHits must be the FULL match count, computed before top-k
        truncation (regression: with_totals used to see only k rows),
        and identical for every variant vs the numpy oracle."""
        d_pad = 600
        # deterministic postings: term t matches 200 docs starting at 3t
        sizes = [200, 200, 200]
        flat_docs = np.full(sum(sizes) + 64, d_pad, dtype=np.int32)
        flat_imp = np.zeros(sum(sizes) + 64, dtype=np.float32)
        ext, pos = [], 0
        for t, sz in enumerate(sizes):
            flat_docs[pos:pos + sz] = np.arange(3 * t, 3 * t + sz,
                                                dtype=np.int32)
            flat_imp[pos:pos + sz] = seeded_np.uniform(
                0.1, 1.0, size=sz).astype(np.float32)
            ext.append((pos, sz))
            pos += sz
        rows = [[(ext[t][0], ext[t][1], 1.0 + 0.3 * t, t)
                 for t in range(3)],
                [(ext[t][0], ext[t][1], 1.0, t) for t in range(3)]]
        mins = [1, 2]
        expected = brute_force(rows, flat_docs, flat_imp, d_pad, mins)
        k = 5  # far below the expected match counts
        assert len(expected[0]) > k and len(expected[1]) > k
        _, _, totals = run_kernel(flat_docs, flat_imp, rows, mins,
                                  d_pad, k, with_counts=True,
                                  with_totals=True, variant=variant,
                                  ext=ext)
        assert totals.tolist() == [len(e) for e in expected]


def host_skip_rate(plan, code16, block_max, blk, slot_terms, k):
    """Numpy replica of the kernel's block-max skip decision (same
    formula, same clamps) → fraction of valid 128-lane groups skipped.
    The device mask isn't observable from outside the jit, so tests
    measure engagement through this mirror."""
    blksz = sparse.COMPRESSED_BLOCK
    n_grp = (plan.max_len + blksz - 1) // blksz
    r, t = plan.starts.shape
    bm = np.zeros((r, t, n_grp + 1), np.uint16)
    for ri in range(r):
        for ti in range(t):
            s = min(int(blk[ri, ti]), block_max.size - (n_grp + 1))
            bm[ri, ti] = block_max[s:s + n_grp + 1]
    grp_code = np.maximum(bm[..., :-1], bm[..., 1:]).astype(np.uint32)
    ub = (np.minimum(grp_code + 1, 0x7F80) << 16).view(np.float32)
    ub = ub.reshape(grp_code.shape)
    g_valid = ((np.arange(n_grp) * blksz)[None, None, :]
               < plan.lengths[:, :, None])
    w3 = plan.weights[:, :, None]
    grp_ub = np.where(g_valid & (w3 > 0), w3 * ub, 0.0)
    slot_ub = grp_ub.max(axis=2)
    eq = slot_terms[:, :, None] == slot_terms[:, None, :]
    term_ub = np.where(eq, slot_ub[:, None, :], 0.0).max(axis=2)
    tri = np.tril(np.ones((t, t), bool), k=-1)
    first = ~np.any(eq & tri[None], axis=2)
    others = (np.where(first, term_ub, 0.0).sum(axis=1, keepdims=True)
              - term_ub)
    thr = np.full(r, -np.inf, np.float32)
    for ri in range(r):
        for ti in range(t):
            ln = int(plan.lengths[ri, ti])
            if ln >= k:
                s = int(plan.starts[ri, ti])
                q = plan.weights[ri, ti] * (
                    (code16[s:s + ln].astype(np.uint32) << 16)
                    .view(np.float32))
                thr[ri] = max(thr[ri], np.partition(q, -k)[-k])
    skip = (grp_ub + others[:, :, None]) < thr[:, None, None]
    return float((skip & g_valid).sum()) / max(1, int(g_valid.sum()))


def make_heavy_flat(rng, d_pad, dfs, skew=3.0):
    """Long skewed postings — the regime where block-max elimination has
    something to eliminate (most blocks' maxima sit far below the k-th
    best score)."""
    docs_all, imps_all, ext = [], [], []
    pos = 0
    for df in dfs:
        ds = np.sort(rng.choice(d_pad, size=df,
                                replace=False)).astype(np.int32)
        im = (rng.random(df).astype(np.float32) ** skew * 0.9
              + 0.01).astype(np.float32)
        docs_all.append(ds)
        imps_all.append(im)
        ext.append((pos, df))
        pos += df
    flat_docs = np.concatenate(
        docs_all + [np.full(4352, d_pad, np.int32)])
    flat_imp = np.concatenate(imps_all + [np.zeros(4352, np.float32)])
    return flat_docs, flat_imp, ext


@pytest.mark.compressed_pack
class TestCompressedPack:
    """Compressed resident streams: exact rank-table round-trip, the
    compressibility gates, and the pruning-safety property — block-max
    skipping must never drop a true top-k document (bitwise equality vs
    the reference scorer IS that assertion)."""

    def test_rank_stream_roundtrip_exact(self, seeded_np):
        d_pad = 2000
        flat_docs, flat_imp, ext = make_flat(seeded_np, 5, d_pad, 600)
        # tie-heavy quantization + tombstones: ranks must still decode
        # every positive impact exactly
        flat_imp = (np.ceil(flat_imp * 8.0) / 8.0).astype(np.float32)
        flat_imp[ext[1][0]: ext[1][0] + ext[1][1]: 5] = 0.0
        rs = row_starts_of(ext, flat_docs.size)
        docs16, code16, rank16, block_max, res_vals, res_rs = \
            sparse.compress_flat(flat_docs, flat_imp, rs, d_pad)
        n_terms = len(ext)
        terms = np.repeat(np.arange(n_terms), np.diff(rs))
        terms = np.concatenate(
            [terms, np.full(flat_imp.size - terms.size, n_terms - 1)])
        at = res_rs[terms] + rank16.astype(np.int64) - 1
        dec = np.where(rank16 > 0,
                       res_vals[np.minimum(at, res_vals.size - 1)], 0.0)
        np.testing.assert_array_equal(
            dec.astype(np.float32),
            np.where(flat_imp > 0, flat_imp, 0.0).astype(np.float32))
        # doc stream: identical inside rows (pad lanes clamp to d_pad)
        np.testing.assert_array_equal(
            docs16[:rs[-1]].astype(np.int32), flat_docs[:rs[-1]])
        # code stream: monotone lower bound of the exact impact
        dec_code = (code16[:rs[-1]].astype(np.uint32) << 16) \
            .view(np.float32)
        assert (dec_code <= flat_imp[:rs[-1]]).all()

    def test_compress_gates(self, seeded_np):
        flat_docs, flat_imp, ext = make_flat(seeded_np, 2, 500, 100)
        rs = row_starts_of(ext, flat_docs.size)
        assert sparse.compress_reason(flat_docs, flat_imp, rs, 500) is None
        # doc axis past the 16-bit range
        assert "doc" in sparse.compress_reason(
            flat_docs, flat_imp, rs, sparse.PACKED_DOC_LIMIT)
        # non-finite and negative impacts
        bad = flat_imp.copy()
        bad[3] = np.inf
        assert sparse.compress_reason(flat_docs, bad, rs, 500)
        bad = flat_imp.copy()
        bad[3] = -0.25
        assert sparse.compress_reason(flat_docs, bad, rs, 500)
        # positive impact so small its 16-bit code floors to 0: the
        # quantized total would silently drop the match
        bad = flat_imp.copy()
        bad[3] = 1e-41
        assert "code" in sparse.compress_reason(flat_docs, bad, rs, 500)

    def test_skip_engages_and_preserves_topk(self, seeded_np):
        """Deterministic tier-1 core of the safety sweep: heavy skewed
        postings where the host mirror shows a NONZERO skip-rate, and
        the kernel output stays bit-identical to the reference."""
        d_pad = 20000
        flat_docs, flat_imp, ext = make_heavy_flat(
            seeded_np, d_pad, [9000, 7000, 5000])
        cases = [([0], [1.0], 10),
                 ([0, 1], [5.0, 0.2], 10),
                 ([0, 1, 2], [8.0, 0.1, 0.1], 16)]
        engaged = 0.0
        for tsel, ws, k in cases:
            rows = [[(ext[t][0], ext[t][1], w, t)
                     for t, w in zip(tsel, ws)]]
            plan = sparse.plan_slots(rows, [1], chunk_cap=4096, lane=8)
            _, code16, extra = compressed_operands(
                flat_docs, flat_imp, ext, d_pad, plan)
            engaged += host_skip_rate(
                plan, np.asarray(code16), np.asarray(extra["block_max"]),
                np.asarray(extra["blk_starts"]),
                np.asarray(extra["slot_terms"]), k)
            assert_variants_identical(flat_docs, flat_imp, rows, [1],
                                      d_pad, k, ext=ext)
        assert engaged > 0.0, "block-max skip never engaged"

    @pytest.mark.slow
    def test_pruning_safety_sweep(self, seeded_np):
        """Randomized sweep: skewed/tie-heavy/chunked corpora × OR/msm/
        AND × k — compressed results bitwise equal to the reference in
        every trial, with the skip mirror engaging across the sweep."""
        total_rate = 0.0
        for i in range(15):
            d_pad = int(seeded_np.integers(8000, 40000))
            # every third trial is single-term + skewed + small k — the
            # regime where skipping provably engages, so the engagement
            # assert below holds for ANY suite seed
            n_terms = 1 if i % 3 == 0 else int(seeded_np.integers(1, 5))
            dfs = [int(seeded_np.integers(2000,
                                          min(12000, d_pad - 1)))
                   for _ in range(n_terms)]
            flat_docs, flat_imp, ext = make_heavy_flat(
                seeded_np, d_pad, dfs,
                skew=1.0 if i % 3 == 1 else 3.0)
            if i % 4 == 0:  # tie-heavy: quantized impacts
                flat_imp = np.maximum(
                    np.round(flat_imp * 8) / 8, 0.125).astype(np.float32)
                flat_imp[row_starts_of(ext, 0)[-1]:] = 0.0
            ws = [float(seeded_np.uniform(0.1, 6.0))
                  for _ in range(n_terms)]
            rows = [[(ext[t][0], ext[t][1], ws[t], t)
                     for t in range(n_terms)]]
            mc = int(seeded_np.integers(1, n_terms + 1))
            k = (int(seeded_np.integers(5, 32)) if n_terms == 1
                 else int(seeded_np.integers(1, 100)))
            cap = 1024 if i % 5 == 0 else 4096
            assert_variants_identical(flat_docs, flat_imp, rows, [mc],
                                      d_pad, k, ext=ext, chunk_cap=cap)
            if mc == 1:
                plan = sparse.plan_slots(rows, [1], chunk_cap=cap,
                                         lane=8)
                _, code16, extra = compressed_operands(
                    flat_docs, flat_imp, ext, d_pad, plan)
                total_rate += host_skip_rate(
                    plan, np.asarray(code16),
                    np.asarray(extra["block_max"]),
                    np.asarray(extra["blk_starts"]),
                    np.asarray(extra["slot_terms"]), k)
        assert total_rate > 0.0

    def test_compressed_requires_operands(self, seeded_np):
        flat_docs, flat_imp, ext = make_flat(seeded_np, 2, 400, 80)
        rows = [[(ext[t][0], ext[t][1], 1.0, t) for t in range(2)]]
        plan = sparse.plan_slots(rows, [1], chunk_cap=4096, lane=8)
        with pytest.raises(ValueError, match="compressed"):
            sparse.sorted_merge_topk(
                jnp.asarray(flat_docs.astype(np.uint16)),
                jnp.asarray(flat_imp.astype(np.uint16)),
                jnp.asarray(plan.starts), jnp.asarray(plan.lengths),
                jnp.asarray(plan.weights), jnp.asarray(plan.min_count),
                max_len=plan.max_len, d_pad=400, k=5,
                t_window=plan.window, with_counts=False,
                variant="compressed")

    def test_delta_requires_cursor_operands(self, seeded_np):
        # doc_bases without its slot cursors must be a typed error, not
        # a silent wrong decode
        flat_docs, flat_imp, ext = make_flat(seeded_np, 2, 250, 80)
        rows = [[(ext[t][0], ext[t][1], 1.0, t) for t in range(2)]]
        plan = sparse.plan_slots(rows, [1], chunk_cap=4096, lane=8)
        docs8, code16, extra = compressed_operands(
            flat_docs, flat_imp, ext, 250, plan)
        assert "doc_bases" in extra  # d_pad=250 corpus is delta-eligible
        extra.pop("dbs_starts")
        with pytest.raises(ValueError, match="dbs_starts"):
            sparse.sorted_merge_topk(
                jnp.asarray(docs8), jnp.asarray(code16),
                jnp.asarray(plan.starts), jnp.asarray(plan.lengths),
                jnp.asarray(plan.weights), jnp.asarray(plan.min_count),
                max_len=plan.max_len, d_pad=250, k=5,
                t_window=plan.window, with_counts=False,
                variant="compressed", **extra)

    def test_totals_served_through_skip_path(self, seeded_np):
        """ISSUE 17 satellite: with_totals no longer forces the
        block-max skip off. On this corpus the host mirror shows a
        NONZERO skip rate (it was forced to an unskipped launch
        before), and the totals from the skipping variant are exact —
        bit-identical to the reference and to the oracle count,
        courtesy of the pre-skip count sort."""
        d_pad = 20000
        flat_docs, flat_imp, ext = make_heavy_flat(
            seeded_np, d_pad, [9000, 7000])
        rows = [[(ext[0][0], ext[0][1], 1.0, 0)]]
        k = 10
        plan = sparse.plan_slots(rows, [1], chunk_cap=4096, lane=8)
        _, code16, extra = compressed_operands(
            flat_docs, flat_imp, ext, d_pad, plan)
        rate = host_skip_rate(
            plan, np.asarray(code16), np.asarray(extra["block_max"]),
            np.asarray(extra["blk_starts"]),
            np.asarray(extra["slot_terms"]), k)
        assert rate > 0.0, "corpus must engage the skip for this test"
        rv, rd, rt = run_kernel(flat_docs, flat_imp, rows, [1], d_pad,
                                k, with_totals=True, variant="ref")
        cv, cd, ct = run_kernel(flat_docs, flat_imp, rows, [1], d_pad,
                                k, with_totals=True,
                                variant="compressed", ext=ext)
        np.testing.assert_array_equal(rv.view(np.uint32),
                                      cv.view(np.uint32))
        np.testing.assert_array_equal(rd, cd)
        np.testing.assert_array_equal(rt, ct)
        exp = brute_force(rows, flat_docs, flat_imp, d_pad, [1])[0]
        assert ct.tolist() == [len(exp)]


class TestDeltaDocStream:
    """Per-block delta doc encoding (u16 docs → u8 delta + u16 block
    base): exact roundtrip, the span gate, and full-kernel parity when
    the operands take the delta format."""

    def test_roundtrip_exact(self, seeded_np):
        d_pad = 256  # any 128-lane block trivially spans ≤ 255 ids
        flat_docs, flat_imp, ext = make_flat(seeded_np, 4, d_pad, 200)
        rs = row_starts_of(ext, flat_docs.size)
        assert sparse.delta_doc_reason(flat_docs, rs) is None
        nbd = (flat_docs.size + sparse.COMPRESSED_BLOCK - 1) \
            // sparse.COMPRESSED_BLOCK + 2
        docs8, bases = sparse.delta_encode_docs(flat_docs, rs, nbd)
        assert docs8.dtype == np.uint8 and bases.dtype == np.uint16
        total = int(rs[-1])
        pos = np.arange(total)
        dec = (bases[pos // sparse.COMPRESSED_BLOCK].astype(np.int64)
               + docs8[:total])
        np.testing.assert_array_equal(dec, flat_docs[:total])
        # slack tail encodes to zeros (never decoded by the kernel)
        assert not docs8[total:].any()

    def test_gate_rejects_wide_blocks(self):
        # stride-4 doc ids: every full 128-lane block spans 508 > 255
        d_pad = 4096
        docs = np.arange(0, d_pad, 4, dtype=np.int32)
        flat_docs = np.concatenate(
            [docs, np.full(4352, d_pad, dtype=np.int32)])
        rs = np.array([0, docs.size], dtype=np.int64)
        reason = sparse.delta_doc_reason(flat_docs, rs)
        assert reason is not None and "span" in reason
        with pytest.raises(ValueError, match="delta"):
            sparse.delta_encode_docs(flat_docs, rs, 1024)

    def test_gate_ignores_slack_tail(self):
        # real postings are tight; the d_pad-sentinel tail would blow
        # the span if the gate (wrongly) looked at it
        d_pad = 4096
        docs = np.arange(100, 180, dtype=np.int32)
        flat_docs = np.concatenate(
            [docs, np.full(4352, d_pad, dtype=np.int32)])
        rs = np.array([0, docs.size], dtype=np.int64)
        assert sparse.delta_doc_reason(flat_docs, rs) is None

    @pytest.mark.compressed_pack
    @pytest.mark.parametrize("min_count, chunk_cap", [
        (1, 4096), (3, 4096),
        # tiny chunks: slot cursors land on arbitrary (dbs, dlo) splits
        (1, 64)])
    def test_delta_parity_all_variants(self, seeded_np, min_count,
                                       chunk_cap):
        """A delta-eligible corpus pushes every compressed variant
        through the in-kernel u8 decode; results must stay
        bit-identical to the reference, chunked or not."""
        d_pad = 256
        flat_docs, flat_imp, ext = make_flat(seeded_np, 5, d_pad, 200)
        rs = row_starts_of(ext, flat_docs.size)
        assert sparse.delta_doc_reason(flat_docs, rs) is None
        ws = [1.3, 0.7, 2.2, 0.4, 1.9]
        rows = [[(ext[t][0], ext[t][1], ws[t], t) for t in range(5)]]
        assert_variants_identical(flat_docs, flat_imp, rows, [min_count],
                                  d_pad, 40, ext=ext, chunk_cap=chunk_cap)


class TestHierarchicalTopK:
    def test_matches_flat_topk_with_ties(self, seeded_np):
        import jax.lax
        # block-multiple width with integer scores → massive tie groups
        # split=True: exercise the per-block merge on CPU, where the
        # trace-time default routes to the flat TopK custom call
        score = jnp.asarray(seeded_np.integers(
            0, 50, size=(3, 8192)).astype(np.float32))
        for k in (1, 32, 100):
            hv, hp = sparse.hierarchical_top_k(score, k, split=True)
            fv, fp = jax.lax.top_k(score, k)
            np.testing.assert_array_equal(np.asarray(hv), np.asarray(fv))
            np.testing.assert_array_equal(np.asarray(hp), np.asarray(fp))

    def test_fallback_widths(self, seeded_np):
        import jax.lax
        # narrow and non-block-multiple widths fall back to flat top_k
        for width in (7, 4095, 4097):
            score = jnp.asarray(
                seeded_np.normal(size=(2, width)).astype(np.float32))
            hv, hp = sparse.hierarchical_top_k(score, 5, split=True)
            fv, fp = jax.lax.top_k(score, 5)
            np.testing.assert_array_equal(np.asarray(hv), np.asarray(fv))
            np.testing.assert_array_equal(np.asarray(hp), np.asarray(fp))


class TestServingWidth:
    """The 32-slot full-precision serving bucket, 32 x CHUNK_CAP =
    131,072 lanes a row: what holds there is checked as equalities. A
    speed is a cell's to read (`device_full_s32_ms_per_launch`)."""

    ROWS = 2
    T_SLOTS = 32
    MAX_LEN = 4096
    K = 128

    def test_packed_bit_identical_to_ref_at_serving_width(self, seeded_np):
        d_pad, df = 60000, 3500
        flat_len = (self.T_SLOTS + 1) * self.MAX_LEN  # chunk-cap slack
        fd = np.full(flat_len, d_pad, dtype=np.int32)
        fi = np.zeros(flat_len, dtype=np.float32)
        starts = np.zeros((self.ROWS, self.T_SLOTS), np.int32)
        lengths = np.full((self.ROWS, self.T_SLOTS), df, np.int32)
        weights = np.zeros((self.ROWS, self.T_SLOTS), np.float32)
        for t in range(self.T_SLOTS):
            pos = t * df
            fd[pos:pos + df] = np.sort(seeded_np.choice(
                d_pad, df, replace=False)).astype(np.int32)
            fi[pos:pos + df] = seeded_np.uniform(
                0.1, 1.0, df).astype(np.float32)
            starts[:, t] = pos
            weights[:, t] = seeded_np.uniform(0.5, 3.0)
        operands = tuple(jnp.asarray(x) for x in (
            fd, fi, starts, lengths, weights,
            np.ones(self.ROWS, np.int32)))

        def run(variant):
            return [np.asarray(x) for x in sparse.sorted_merge_topk(
                *operands, max_len=self.MAX_LEN, d_pad=d_pad, k=self.K,
                t_window=self.T_SLOTS, with_counts=False,
                with_totals=True, variant=variant)]

        rv, rd, rt = run("ref")
        pv, pd_, pt = run("packed")
        np.testing.assert_array_equal(rv.view(np.uint32),
                                      pv.view(np.uint32))
        np.testing.assert_array_equal(rd, pd_)
        np.testing.assert_array_equal(rt, pt)

    def test_topk_dispatch_equals_flat_and_splits_only_on_tpu(
            self, seeded_np, monkeypatch):
        import jax
        width = self.T_SLOTS * self.MAX_LEN
        score = jnp.asarray(seeded_np.normal(
            size=(self.ROWS, width)).astype(np.float32))
        fv, fp = jax.lax.top_k(score, self.K)
        hv, hp = sparse.hierarchical_top_k(score, self.K)
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(hv))
        np.testing.assert_array_equal(np.asarray(fp), np.asarray(hp))

        def top_k_operand_shapes():
            jaxpr = jax.make_jaxpr(
                lambda s: sparse.hierarchical_top_k(s, self.K))(score)
            return [eqn.invars[0].aval.shape for eqn in jaxpr.eqns
                    if eqn.primitive.name == "top_k"]

        # the choice is made at trace time from the backend: flat here
        # (XLA:CPU's TopK is already a selection), per block where
        # top_k lowers to a sort of the full width
        assert top_k_operand_shapes() == [(self.ROWS, width)]
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert top_k_operand_shapes() == [
            (self.ROWS, self.T_SLOTS, self.MAX_LEN),
            (self.ROWS, self.T_SLOTS * self.K)]


class TestPlanSlots:
    def test_chunk_cap_never_exceeded(self):
        # non-power-of-two cap rounds DOWN (callers size flat-array slack
        # to the cap; a bigger bucket would overrun it)
        rows = [[(0, 3000, 1.0, 0)]]
        plan = sparse.plan_slots(rows, [1], chunk_cap=3000, lane=128)
        assert plan.max_len <= 3000
        assert plan.max_len == 2048
        assert plan.window == 1  # one term, chunks don't widen the window

    def test_window_counts_terms_not_chunks(self):
        rows = [[(0, 100, 1.0, 0), (100, 50, 1.0, 1)]]
        plan = sparse.plan_slots(rows, [1], chunk_cap=16, lane=8)
        assert plan.t_slots >= 8  # many chunks
        assert plan.window == 2   # but only 2 terms
