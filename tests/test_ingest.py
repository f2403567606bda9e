"""Out-of-core ingestion pipeline (DESIGN.md §9): chunked-source
determinism, streaming-merge weight exactness, prefetch-feed behavior, and
select->fit equivalence against the in-memory paths."""
import time

import numpy as np
import pytest

from repro.core import gaussian
from repro.core.ingest_pipeline import (_PrefetchFeed, IngestStats,
                                        ingest_fit, pad_block,
                                        select_streaming)
from repro.core.shadow import shadow_select_blocked
from merge_reference import StreamingMerge
from repro.data.kpca_datasets import ChunkedDataset


def test_chunked_source_deterministic_across_chunk_sizes():
    """Row i depends only on (name, seed, i): chunk size and total n must
    not change a single shared row's bits."""
    a = ChunkedDataset("pendigits", n=9000, chunk=4096, seed=3).materialize()
    b = ChunkedDataset("pendigits", n=9000, chunk=1000, seed=3).materialize()
    c = ChunkedDataset("pendigits", n=9000, chunk=9000, seed=3).materialize()
    assert np.array_equal(a, b) and np.array_equal(a, c)
    # a LONGER stream agrees bit-exactly on the shared prefix
    big = ChunkedDataset("pendigits", n=50000, chunk=8192, seed=3)
    assert np.array_equal(a, big.rows(0, 9000))
    # different seeds genuinely differ
    d = ChunkedDataset("pendigits", n=9000, chunk=4096, seed=4).materialize()
    assert not np.array_equal(a, d)


def test_chunked_source_ragged_final_chunk():
    src = ChunkedDataset("pendigits", n=10000, chunk=4096, seed=0)
    chunks = list(src.chunks())
    assert [nv for _, nv in chunks] == [4096, 4096, 1808]
    for x, nv in chunks:
        assert x.shape == (4096, src.d) and x.dtype == np.float32
        assert (x[nv:] == 0).all()  # padding rows are zero (and masked)
    got = np.concatenate([x[:nv] for x, nv in chunks])
    assert np.array_equal(got, src.materialize())


def test_chunked_source_stream_matches_make_dataset_geometry():
    """Same mixture family: bandwidth of the stream's prefix sample is a
    sane, positive sigma (the ingest bench derives eps from it)."""
    src = ChunkedDataset("pendigits", n=4096, chunk=1024, seed=0)
    assert src.bandwidth() > 0
    assert src.nbytes_f32 == 4 * 4096 * 16
    with pytest.raises(AssertionError):
        ChunkedDataset("pendigits", n=1 << 23, chunk=1024).materialize()


def test_pad_block_contract():
    x = np.ones((5, 3), np.float32)
    xp, ok = pad_block(x, 8)
    assert xp.shape == (8, 3) and ok.sum() == 5 and (xp[5:] == 0).all()
    xf, okf = pad_block(x, 5)  # full block: no copy, mask all-true
    assert okf.all() and np.array_equal(xf, x)
    with pytest.raises(AssertionError):
        pad_block(x, 4)


def _chunks_of(x, chunk):
    """Bare-iterable source protocol: (fixed-shape block, n_valid)."""
    for s in range(0, len(x), chunk):
        yield pad_block(x[s : s + chunk], chunk)[0], min(chunk, len(x) - s)


def _mix(n, d=6, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, 1, (max(2, n // 30), d))
    idx = rng.integers(0, centers.shape[0], n)
    return (centers[idx] + 0.05 * rng.normal(size=(n, d))).astype(np.float32)


def test_single_chunk_matches_blocked_exactly():
    """chunk >= n on one device: the stream is a single blocked selection
    and the merge must pass its centers/weights through UNCHANGED."""
    x = _mix(700)
    for eps in (0.1, 0.3):
        rsde, stats = select_streaming(_chunks_of(x, 1024), eps, block=64)
        c, w, _, m = shadow_select_blocked(x, eps, block=64)
        assert rsde.centers.shape[0] == m and stats.m == m
        np.testing.assert_array_equal(rsde.centers, c[:m])
        np.testing.assert_allclose(rsde.weights, w[:m])
        assert rsde.weights.sum() == len(x)  # exact, not approx


def test_multichunk_weight_exact_and_2eps_cover():
    x = _mix(1500, seed=5)
    eps = 0.2
    rsde, stats = select_streaming(_chunks_of(x, 256), eps, block=32)
    assert stats.chunks == 6 and stats.rows == 1500
    assert rsde.weights.dtype == np.float64
    assert rsde.weights.sum() == 1500.0  # EXACT f64 mass bookkeeping
    assert (rsde.weights > 0).all()
    d = np.linalg.norm(x[:, None] - rsde.centers[None], axis=2).min(1)
    assert (d < 2 * eps + 1e-5).all()
    # merged centers stay pairwise >= eps apart (absorb-then-select)
    if rsde.m > 1:
        d2 = ((rsde.centers[:, None] - rsde.centers[None]) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        assert np.sqrt(d2.min()) >= eps - 1e-5


def test_unbudgeted_merge_state_follows_m_not_rows():
    """Without a budget the device state grows with m and a chunk, not
    with the rows ingested: a long stream over few clusters keeps a small
    state, and its merge equals the plain host merge of the same chunks'
    candidates, batch for batch."""
    rng = np.random.default_rng(13)
    centers = rng.uniform(0, 1, (6, 4))
    x = (centers[rng.integers(0, 6, 6144)]
         + 0.02 * rng.normal(size=(6144, 4))).astype(np.float32)
    eps, chunk = 0.2, 128
    rsde, stats = select_streaming(_chunks_of(x, chunk), eps, block=32)
    assert stats.chunks == 48 and rsde.weights.sum() == len(x)
    assert stats.cap <= 4 * (stats.m + chunk) and stats.cap < len(x) // 4
    ref = StreamingMerge(x.shape[1], eps, block=32)
    for c, w in _candidate_batches(x, eps, chunk):
        ref.update(c, w)
    np.testing.assert_array_equal(rsde.centers, ref.centers)
    np.testing.assert_array_equal(rsde.weights, ref.weights)


def test_budget_spill_caps_m_exactly():
    x = _mix(1200, seed=9)
    eps = 0.05  # tiny eps -> many more candidates than the budget
    rsde, stats = select_streaming(_chunks_of(x, 256), eps, block=32,
                                   budget=40)
    assert rsde.m == 40  # deterministic m under the budget
    assert stats.spilled > 0 and stats.max_spill_dist > 0
    assert rsde.weights.sum() == 1200.0  # spill hands mass over exactly
    un_capped, _ = select_streaming(_chunks_of(x, 256), eps, block=32)
    assert un_capped.m > 40


def test_ragged_and_empty_tail_chunks():
    """A final chunk with few valid rows — and an all-padding chunk — must
    neither crash nor perturb the mass invariant."""
    x = _mix(300, seed=2)
    chunks = list(_chunks_of(x, 128))  # valid: 128, 128, 44
    chunks.append((np.zeros_like(chunks[0][0]), 0))  # fully-empty chunk
    rsde, stats = select_streaming(iter(chunks), 0.2, block=16)
    assert stats.rows == 300 and rsde.weights.sum() == 300.0


def test_prefetch_feed_stats_and_order():
    stats = IngestStats()
    items = [(np.full((4, 2), i, np.float32), 4) for i in range(7)]
    out = list(_PrefetchFeed(iter(items), lambda x, nv: (x, nv), stats,
                             depth=3))
    assert [int(x[0, 0]) for x, _ in out] == list(range(7))  # order kept
    assert stats.feed_s >= 0 and stats.stall_s >= 0


def test_prefetch_feed_propagates_producer_error():
    def bad_source():
        yield np.zeros((4, 2), np.float32), 4
        raise RuntimeError("disk on fire")

    stats = IngestStats()
    with pytest.raises(RuntimeError, match="disk on fire"):
        list(_PrefetchFeed(bad_source(), lambda x, nv: (x, nv), stats))


def test_select_streaming_empty_source_raises():
    with pytest.raises(ValueError, match="empty source"):
        select_streaming(iter([]), 0.1)


def _device_merge(batches, d, eps, budget=None, block=32):
    """The on-device streaming merge fed ``batches`` of (centers, int
    masses) one bucket each; returns its host view."""
    import jax.numpy as jnp

    from repro.core import shadow

    rows = sum(len(w) for _, w in batches)
    state = shadow.merge_init(d, shadow.merge_capacity(max(rows, 1), budget))
    for c, w in batches:
        k = max(len(w), 1)
        cp = np.zeros((k, d), np.float32)
        cp[: len(w)] = c
        wp = np.zeros((k,), np.int32)
        wp[: len(w)] = w
        state = shadow._merge_chunk(state, jnp.asarray(cp), jnp.asarray(wp),
                                    jnp.float32(eps) ** 2, block, budget)
    return shadow.merge_to_host(state)


def _candidate_batches(x, eps, chunk, block=32):
    out = []
    for s in range(0, len(x), chunk):
        c, w, _, m = shadow_select_blocked(x[s : s + chunk], eps, block=block)
        out.append((c[:m], w[:m].astype(np.int64)))
    return out


@pytest.mark.parametrize("budget", [None, 40])
def test_device_merge_matches_host_reference(budget):
    """Batch for batch, the on-device merge keeps the centers and int
    masses the plain host merge keeps, spilling the same candidates under a
    budget; the merged set partitions the mass exactly, stays
    eps-separated and, within no budget, covers every row within 2*eps."""
    x = _mix(1200, seed=9)
    eps = 0.05 if budget else 0.2
    batches = _candidate_batches(x, eps, 256)
    ref = StreamingMerge(x.shape[1], eps, budget=budget, block=32)
    for c, w in batches:
        ref.update(c, w)
    got = _device_merge(batches, x.shape[1], eps, budget=budget)
    np.testing.assert_array_equal(got["centers"], ref.centers)
    np.testing.assert_array_equal(got["weights"], ref.weights)
    assert got["weights"].sum() == len(x)
    cnt = dict(zip(("m", "gathered", "absorbed", "kept", "spilled"),
                   got["counters"]))
    assert cnt["m"] == ref.m and cnt["spilled"] == ref.spilled
    assert cnt["gathered"] == sum(len(w) for _, w in batches)
    assert cnt["kept"] == ref.m
    if budget:
        assert ref.m == budget and ref.spilled > 0
        np.testing.assert_allclose(np.sqrt(got["pairs"][2]),
                                   ref.max_spill_dist, rtol=1e-6)
    else:
        d = np.linalg.norm(x[:, None] - got["centers"][None], axis=2).min(1)
        assert (d < 2 * eps + 1e-5).all()
    d2 = ((got["centers"][:, None] - got["centers"][None]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    assert np.sqrt(d2.min()) >= eps - 1e-5


def test_device_merge_masses_exact_past_2_24():
    """Masses are int32 on the device: duplicate candidates whose masses
    sum past 2^24 merge to the exact integer, where float32 rounds."""
    c = np.eye(3, dtype=np.float32)
    big = np.array([(1 << 23) + 1, (1 << 23) + 3, 5], np.int64)
    got = _device_merge([(c, big)] * 3, 3, 0.2)
    exact = 3 * big
    np.testing.assert_array_equal(got["weights"], exact)
    assert got["weights"].sum() == 3 * big.sum()
    f32 = np.float32(big[0]) + np.float32(big[0]) + np.float32(big[0])
    assert int(f32) != exact[0]  # what a float32 accumulator would give


def test_streaming_merge_duplicate_centers_across_batches():
    """The same centers arriving from two chunk/shard boundaries must merge
    (d2 == 0 < eps^2), not accumulate as near-duplicates."""
    x = _mix(400, seed=11)
    c, w, _, m = shadow_select_blocked(x, 0.2, block=32)
    batch = (c[:m], w[:m].astype(np.int64))
    got = _device_merge([batch, batch], x.shape[1], 0.2)  # identical again
    assert got["centers"].shape[0] == m
    np.testing.assert_array_equal(got["centers"], c[:m])
    assert got["weights"].sum() == 2 * len(x)


def test_streaming_merge_empty_and_padded_updates():
    empty = (np.zeros((0, 3), np.float32), np.zeros((0,), np.int64))
    padding = (np.zeros((5, 3), np.float32), np.zeros((5,), np.int64))
    got = _device_merge([empty, padding], 3, 0.2)
    assert got["centers"].shape[0] == 0 and got["counters"].sum() == 0
    got = _device_merge([empty, padding,
                         (np.eye(3, dtype=np.float32), np.ones(3, np.int64))],
                        3, 0.2)
    assert got["centers"].shape[0] == 3 and got["weights"].sum() == 3


def test_ingest_fit_matches_fused_fit_on_one_chunk():
    """Single-chunk stream: ingest_fit and the fused blocked-selection loop
    run to exhaustion (``_blocked_select_device``), then ``fit_centers`` on
    its device buffers, see the exact same center set, so the fitted models
    must embed identically."""
    import jax.numpy as jnp
    from repro.core.pipeline import fit_centers
    from repro.core.shadow import _blocked_select_device

    x = _mix(600, seed=4)
    sigma = float(np.median(np.linalg.norm(x[:50, None] - x[None, :50],
                                           axis=2)))
    ker = gaussian(sigma)
    eps2 = jnp.float32(ker.epsilon(3.0)) ** 2
    _, c, w, _, mr = _blocked_select_device(
        jnp.asarray(x), eps2, 64, jnp.ones((len(x),), bool),
        jnp.asarray(0, jnp.int32))
    model_f = fit_centers(c, w, len(x), ker, 4, m=int(np.asarray(mr)[0]))
    model_i, stats = ingest_fit(_chunks_of(x, 1024), ker, 4, ell=3.0,
                                block=64)
    assert model_i.method == "rskpca+shadow-ingest"
    assert stats.wall_s > 0 and stats.fit_s > 0
    np.testing.assert_array_equal(model_i.centers, model_f.centers)
    q = x[:64]
    np.testing.assert_allclose(model_i.transform(q), model_f.transform(q),
                               atol=1e-5)


def test_ingest_fit_multichunk_end_to_end():
    src = ChunkedDataset("pendigits", n=6000, chunk=2048, seed=1)
    ker = gaussian(src.bandwidth())
    model, stats = ingest_fit(src, ker, 6, ell=3.0, block=64, budget=256)
    assert model.centers.shape[0] == stats.m <= 256
    assert stats.rows == 6000 and stats.chunks == 3
    assert 0.0 <= stats.overlap_fraction <= 1.0
    assert stats.rows_per_s > 0
    z = model.transform(src.rows(0, 100))
    assert z.shape == (100, 6) and np.isfinite(z).all()


def test_overlap_fraction_edge_cases():
    """The overlap metric must stay in [0, 1] at the degenerate corners:
    an all-cached feed (feed_s == 0) counts as fully hidden, and a stall
    measured LONGER than the feed work (clock skew between the producer
    and consumer threads) clips to 0 instead of going negative."""
    assert IngestStats(feed_s=0.0, stall_s=0.0).overlap_fraction == 1.0
    assert IngestStats(feed_s=0.0, stall_s=0.5).overlap_fraction == 1.0
    assert IngestStats(feed_s=1.0, stall_s=2.0).overlap_fraction == 0.0
    assert IngestStats(feed_s=2.0, stall_s=0.5).overlap_fraction \
        == pytest.approx(0.75)


def test_prefetch_feed_excludes_queue_blocking_from_feed_s():
    """feed_s is producer WORK, not producer waiting: with an instant
    source and a slow consumer, the producer spends almost all its wall
    time blocked on the full queue, and none of that may count as feed
    time (else overlap_fraction would read ~0 for a pipeline whose feed is
    actually infinitely ahead of compute)."""
    stats = IngestStats()
    items = [(np.zeros((4, 2), np.float32), 4) for _ in range(8)]
    feed = _PrefetchFeed(iter(items), lambda x, nv: (x, nv), stats, depth=2)
    consumer_s = 0.0
    n_out = 0
    for _ in feed:
        t0 = time.perf_counter()
        time.sleep(0.05)  # slow consumer: the queue stays full
        consumer_s += time.perf_counter() - t0
        n_out += 1
    assert n_out == 8 and consumer_s > 0.3
    # producer was blocked ~consumer_s total; its recorded work is tiny
    assert stats.feed_s < 0.5 * consumer_s
    assert stats.feed_s < 0.1
