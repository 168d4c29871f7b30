"""Block-structured merge table: differential pins against the flat
kernel, the scalar engine, and the Pallas twin.

The contract (ISSUE 2 / VERDICT r5 next-round #1): the block kernel ≡
the flat per-op kernel ≡ the scalar MergeEngine byte-identically on the
same sequenced streams — live client streams from the real stack plus
randomized concurrent-ref streams — with the per-block summaries exact
(incremental updates ≡ from-scratch rebuild) and overflow atomic
(first failed op index reported, state frozen at the pre-overflow
frontier, flat-kernel tail replay converging to the same table).
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.drivers.local_driver import LocalDocumentService
from fluidframework_tpu.ops import mergetree_blocks as mtb
from fluidframework_tpu.ops import mergetree_blocks_pallas as mtbp
from fluidframework_tpu.ops import mergetree_kernel as mtk
from fluidframework_tpu.runtime.container import Container
from fluidframework_tpu.server.local_server import LocalCollabServer
from tests.test_mergetree import get_string, make_string_doc, random_edit
from tests.test_mergetree_kernel import encode_log


def gen_stream(rng, n_ops, max_ref_lag=4, annotate=True):
    """Sequenced stream with genuinely concurrent refs (ref lags seq)."""
    ops, length, pool = [], 0, 0
    for seq in range(1, n_ops + 1):
        client = rng.randrange(5)
        ref_seq = rng.randrange(max(seq - max_ref_lag, 0), seq)
        if length > 4 and rng.random() < 0.45:
            start = rng.randrange(length - 2)
            end = start + rng.randint(0, min(4, length - start))
            kind = rng.choice([mtk.MT_REMOVE, mtk.MT_ANNOTATE]) \
                if annotate else mtk.MT_REMOVE
            op = dict(kind=kind, pos=start, end=end, seq=seq,
                      ref_seq=ref_seq, client=client)
            if kind == mtk.MT_ANNOTATE:
                op.update(prop_key=rng.randrange(2),
                          prop_val=rng.randrange(1, 5))
            else:
                length -= end - start
            ops.append(op)
        else:
            tlen = rng.randint(1, 4)
            ops.append(dict(kind=mtk.MT_INSERT, pos=rng.randint(0, length),
                            seq=seq, ref_seq=ref_seq, client=client,
                            pool_start=pool, text_len=tlen))
            pool += tlen
            length += tlen
    return ops


def occupied_rows(flat: mtk.MergeState, doc: int) -> list[tuple]:
    """Every occupied slot's full plane tuple in document order —
    tombstones, overlap words and prop slots included. Gaps (block
    tails) are skipped, so flat and block tables compare directly."""
    valid = np.asarray(flat.valid[doc])
    cols = {f: np.asarray(getattr(flat, f)[doc])
            for f in ("length", "ins_seq", "ins_client", "rem_seq",
                      "rem_client", "pool_start")}
    over = np.asarray(flat.rem_overlap[doc])
    props = np.asarray(flat.prop_val[doc])
    return [tuple(int(cols[f][i]) for f in cols)
            + (tuple(over[i]), tuple(props[i]))
            for i in range(valid.shape[0]) if valid[i]]


def drive(streams, k, flat_state, block_state, rebalance_every=1):
    """Apply the same chunked tick sequence to both kernels; rebalance
    the block table between ticks the way the serving host does."""
    n_docs = len(streams)
    longest = max(len(s) for s in streams)
    for t, start in enumerate(range(0, longest, k)):
        chunk = [s[start:start + k] for s in streams]
        batch = mtk.make_merge_op_batch(chunk, n_docs, k)
        flat_state = mtk.apply_tick(flat_state, batch)
        block_state, ovf = mtb.apply_tick_blocks(block_state, batch)
        assert np.all(np.asarray(ovf) == int(mtb.OVF_NONE)), (t, ovf)
        if (t + 1) % rebalance_every == 0:
            block_state = mtb.rebalance(
                block_state, jnp.zeros((n_docs,), jnp.int32))
    return flat_state, block_state


@pytest.mark.parametrize("seed", range(2))
def test_blocks_match_replicas_on_live_streams(seed):
    """The existing fuzz streams: live SharedString replicas over the
    local server; the block kernel replays the sequenced log and must
    reproduce the converged text byte-for-byte (and agree with the flat
    kernel slot-for-slot)."""
    rng = random.Random(seed)
    n_docs = 3
    server = LocalCollabServer()
    docs = []
    for d in range(n_docs):
        c1 = make_string_doc(server, f"doc{d}")
        others = [Container.load(LocalDocumentService(server, f"doc{d}"))
                  for _ in range(2)]
        docs.append([c1] + others)

    for _round in range(5):
        for containers in docs:
            paused = [c for c in containers if rng.random() < 0.3]
            for c in paused:
                c.inbound.pause()
            for _ in range(rng.randrange(3, 8)):
                random_edit(rng, get_string(
                    containers[rng.randrange(len(containers))]))
            for c in paused:
                c.inbound.resume()

    pool = mtk.TextPool(n_docs)
    client_slots: dict = {}
    key_slots: dict = {}
    val_ids: dict = {}
    streams = [encode_log(server.get_deltas(f"doc{d}", 0), pool, d,
                          client_slots, key_slots, val_ids)
               for d in range(n_docs)]
    flat, block = drive(
        streams, k=16,
        flat_state=mtk.init_state(n_docs, num_slots=512),
        block_state=mtb.init_state(n_docs, num_blocks=16, block_slots=32))
    for d in range(n_docs):
        expected = get_string(docs[d][0]).get_text()
        got = mtb.materialize(block, pool, d).replace("\x00", "")
        assert got == expected, (seed, d)
        assert got == mtk.materialize(flat, pool, d).replace("\x00", "")


@pytest.mark.parametrize("seed", range(6))
def test_blocks_match_flat_slot_level(seed):
    """Random concurrent-ref streams: every occupied slot — live AND
    tombstoned, overlap bitmasks and prop planes included — matches the
    flat kernel in document order, across interleaved rebalances."""
    rng = random.Random(7100 + seed)
    n_docs = rng.choice([1, 4])
    streams = [gen_stream(rng, rng.randrange(16, 60))
               for _ in range(n_docs)]
    flat, block = drive(
        streams, k=8,
        flat_state=mtk.init_state(n_docs, num_slots=512, num_props=2),
        block_state=mtb.init_state(n_docs, num_blocks=8, block_slots=64,
                                   num_props=2))
    # Rebalance drops nothing at min_seq 0, so occupied slots (incl.
    # tombstones) must be identical slot-for-slot.
    view = mtb.flat_view(block)
    for d in range(n_docs):
        assert occupied_rows(view, d) == occupied_rows(flat, d), (seed, d)


@pytest.mark.parametrize("seed", range(4))
def test_summaries_never_drift(seed):
    """The per-op incremental summary updates are exact: after every
    tick the carried summaries equal a from-scratch rebuild (the device
    analog of the scalar engine's settled-block invariant)."""
    rng = random.Random(7200 + seed)
    stream = gen_stream(rng, 48, max_ref_lag=5)
    state = mtb.init_state(1, num_blocks=4, block_slots=128, num_props=2)
    for start in range(0, 48, 8):
        batch = mtk.make_merge_op_batch([stream[start:start + 8]], 1, 8)
        state, ovf = mtb.apply_tick_blocks(state, batch)
        assert int(np.asarray(ovf)[0]) == int(mtb.OVF_NONE)
        rebuilt = mtb.recompute_summaries(state)
        for f in ("blk_live_len", "blk_max_seq", "blk_tomb", "count"):
            assert np.array_equal(np.asarray(getattr(state, f)),
                                  np.asarray(getattr(rebuilt, f))), \
                (seed, start, f)


@pytest.mark.parametrize("seed", range(3))
def test_pallas_twin_bit_identical(seed):
    """The VMEM twin (interpret mode off-TPU) reproduces every plane,
    every summary and the overflow index bit-for-bit."""
    rng = random.Random(7300 + seed)
    n_docs = rng.choice([1, 3])
    streams = [gen_stream(rng, rng.randrange(10, 30))
               for _ in range(n_docs)]
    sx = mtb.init_state(n_docs, num_blocks=8, block_slots=16, num_props=2)
    sp = sx
    longest = max(len(s) for s in streams)
    for start in range(0, longest, 8):
        chunk = [s[start:start + 8] for s in streams]
        batch = mtk.make_merge_op_batch(chunk, n_docs, 8)
        sx, ox = mtb.apply_tick_blocks(sx, batch)
        sp, op_ = mtbp.apply_tick_blocks_pallas(
            sp, batch, interpret=mtbp.default_interpret())
        assert np.array_equal(np.asarray(ox), np.asarray(op_))
        for f in mtb.BlockMergeState._fields:
            assert np.array_equal(np.asarray(getattr(sx, f)),
                                  np.asarray(getattr(sp, f))), (seed, f)
        # Shared rebalance keeps both twins inside block capacity.
        sx = mtb.rebalance(sx, jnp.zeros((n_docs,), jnp.int32))
        sp = sx


@pytest.mark.parametrize("seed", range(2))
def test_rebalance_preserves_future_resolution(seed):
    """Rebalance (incl. tombstone collection under an advanced window)
    must not change how FUTURE concurrent ops resolve — the block
    zamboni twin of test_compact_coalesce_preserves_semantics."""
    rng = random.Random(7400 + seed)
    history = gen_stream(rng, 64, max_ref_lag=1, annotate=False)
    ms = max(op["seq"] for op in history)
    pool_top = sum(op.get("text_len", 0) for op in history)

    flat = mtk.apply_tick(mtk.init_state(1, 512),
                          mtk.make_merge_op_batch([history], 1, 64))
    block, ovf = mtb.apply_tick_blocks(
        mtb.init_state(1, num_blocks=4, block_slots=256),
        mtk.make_merge_op_batch([history], 1, 64))
    assert int(np.asarray(ovf)[0]) == int(mtb.OVF_NONE)

    flat = mtk.compact(flat, jnp.asarray([ms], np.int32))
    block = mtb.rebalance(block, jnp.asarray([ms], np.int32))

    future, flen, fseq, pool = [], 0, ms, pool_top
    flen = int(np.asarray(jnp.sum(mtb.flat_view(block).length
                                  * mtb.flat_view(block).valid)))
    for _ in range(24):
        fseq += 1
        if flen > 8 and rng.random() < 0.4:
            start = rng.randrange(flen - 4)
            end = start + rng.randint(1, 4)
            future.append(dict(kind=mtk.MT_REMOVE, pos=start, end=end,
                               seq=fseq, ref_seq=rng.randint(ms, fseq - 1),
                               client=rng.randrange(4)))
            flen -= end - start
        else:
            tlen = rng.randint(1, 3)
            future.append(dict(kind=mtk.MT_INSERT,
                               pos=rng.randint(0, flen), seq=fseq,
                               ref_seq=rng.randint(ms, fseq - 1),
                               client=rng.randrange(4),
                               pool_start=pool, text_len=tlen))
            pool += tlen
            flen += tlen
    batch = mtk.make_merge_op_batch([future], 1, 32)
    flat2 = mtk.apply_tick(flat, batch)
    block2, ovf = mtb.apply_tick_blocks(block, batch)
    assert int(np.asarray(ovf)[0]) == int(mtb.OVF_NONE)
    view = mtb.flat_view(block2)
    live = [(r[0], r[5]) for r in occupied_rows(view, 0)
            if r[3] == int(mtk.NONE_SEQ)]
    live_flat = [(r[0], r[5]) for r in occupied_rows(flat2, 0)
                 if r[3] == int(mtk.NONE_SEQ)]
    assert live == live_flat, seed


def gen_head_stream(rng, n_ops):
    """Adversarial head-concentrated stream: every structural op lands
    at the document head (the block table's worst shape — the
    incremental-rebalance trigger fires at the maximum rate)."""
    ops, length, pool = [], 0, 0
    for seq in range(1, n_ops + 1):
        if length > 8 and rng.random() < 0.25:
            end = rng.randint(1, 4)
            ops.append(dict(kind=mtk.MT_REMOVE, pos=0, end=end, seq=seq,
                            ref_seq=seq - 1, client=rng.randrange(4)))
            length -= end
        else:
            tlen = rng.randint(1, 4)
            ops.append(dict(kind=mtk.MT_INSERT, pos=0, seq=seq,
                            ref_seq=seq - 1, client=rng.randrange(4),
                            pool_start=pool, text_len=tlen))
            pool += tlen
            length += tlen
    return ops


def gen_tomb_stream(rng, n_ops):
    """Tombstone-heavy: half the ops remove — blk_tomb pressure builds
    toward the deferred-zamboni threshold."""
    ops, length, pool = [], 0, 0
    for seq in range(1, n_ops + 1):
        if length > 6 and rng.random() < 0.5:
            start = rng.randrange(length - 4)
            end = start + rng.randint(1, 4)
            ops.append(dict(kind=mtk.MT_REMOVE, pos=start, end=end,
                            seq=seq, ref_seq=seq - 1,
                            client=rng.randrange(4)))
            length -= end - start
        else:
            tlen = rng.randint(1, 3)
            ops.append(dict(kind=mtk.MT_INSERT,
                            pos=rng.randint(0, length), seq=seq,
                            ref_seq=seq - 1, client=rng.randrange(4),
                            pool_start=pool, text_len=tlen))
            pool += tlen
            length += tlen
    return ops


def _decide(block, k):
    """Host replica of the maybe_rebalance decision ladder (the
    determinism pin: the device must agree with this pure function of
    the state)."""
    nb, bk = block.blk_count.shape[1], block.length.shape[2]
    cap = bk - (2 * k + 2)
    c = np.asarray(block.blk_count)
    danger = bool((c.max(axis=1) + 2 * k + 2 > bk).any())
    e = np.maximum(c - cap, 0)
    e[:, -1] = 0
    c1 = c - e + np.roll(e, 1, axis=-1)
    h = np.maximum(c1 - cap, 0)
    h[:, 0] = 0
    c2 = c1 - h + np.roll(h, -1, axis=-1)
    local_ok = bool((c2 <= cap).all())
    tomb_heavy = bool((np.asarray(block.blk_tomb).sum(axis=1)
                       * mtb.TOMB_PRESSURE_DEN >= nb * bk).any())
    if not danger:
        return 0
    return 1 if (local_ok and not tomb_heavy) else 2


@pytest.mark.parametrize("shape", ["head", "spread", "tomb"])
@pytest.mark.parametrize("seed", range(2))
def test_incremental_rebalance_bit_identical(shape, seed):
    """The round-11 differential fuzz: across head-concentrated, spread
    and tombstone-heavy streams, the incremental spill is a PURE
    re-layout — every occupied slot (tombstones, overlap words, props
    included) stays bit-identical to the flat kernel in document order —
    its summaries never drift from the from-scratch rebuild, the
    per-block headroom truth is restored whenever the table has
    capacity, and the full-rebuild branch is bit-identical to
    ``rebalance`` (≡ flat ``compact`` + ``from_flat``, the pinned
    round-6 contract)."""
    rng = random.Random(7500 + seed)
    gen = {"head": gen_head_stream, "spread": gen_stream,
           "tomb": gen_tomb_stream}[shape]
    stream = gen(rng, 120)
    k, nb, bk = 8, 8, 64
    cap = bk - (2 * k + 2)
    flat = mtk.init_state(1, 1024, num_props=2)
    block = mtb.init_state(1, num_blocks=nb, block_slots=bk, num_props=2)
    zero = jnp.zeros((1,), jnp.int32)
    branches = set()
    for start in range(0, 120, k):
        batch = mtk.make_merge_op_batch([stream[start:start + k]], 1, k)
        flat = mtk.apply_tick(flat, batch)
        block, ovf = mtb.apply_tick_blocks(block, batch)
        assert int(np.asarray(ovf)[0]) == int(mtb.OVF_NONE), (shape, start)
        branch = _decide(block, k)
        branches.add(branch)
        ref_full = mtb.rebalance(block, zero)
        block2, rs = mtb.maybe_rebalance_stats(block, zero, k)
        rs = np.asarray(rs)
        assert (rs[0] == 1) == (branch > 0), (shape, start, branch, rs)
        if branch == 2:
            # Full branch ≡ rebalance() ≡ compact+from_flat, bit-exact.
            for f in mtb.BlockMergeState._fields:
                assert np.array_equal(np.asarray(getattr(block2, f)),
                                      np.asarray(getattr(ref_full, f))), \
                    (shape, start, f)
        if branch == 0:
            for f in mtb.BlockMergeState._fields:
                assert np.array_equal(np.asarray(getattr(block2, f)),
                                      np.asarray(getattr(block, f))), \
                    (shape, start, f)
        # Replay determinism: re-deciding from the same state re-lays
        # out byte-identically (the durable-log replay contract).
        block3, rs3 = mtb.maybe_rebalance_stats(block, zero, k)
        assert np.array_equal(rs, np.asarray(rs3))
        for f in mtb.BlockMergeState._fields:
            assert np.array_equal(np.asarray(getattr(block2, f)),
                                  np.asarray(getattr(block3, f))), \
                (shape, start, f)
        block = block2
        # Summaries never drift through the incremental path.
        rebuilt = mtb.recompute_summaries(block)
        for f in ("blk_live_len", "blk_max_seq", "blk_tomb", "count"):
            assert np.array_equal(np.asarray(getattr(block, f)),
                                  np.asarray(getattr(rebuilt, f))), \
                (shape, start, f)
        # Capacity truth (ADVICE item 4): whenever the table CAN satisfy
        # per-block headroom, the maintenance pass restored it.
        counts = np.asarray(block.blk_count)
        feasible = np.asarray(block.count) <= nb * cap
        assert np.all((counts.max(axis=1) <= cap) | ~feasible), \
            (shape, start, counts)
        # min_seq 0 drops nothing on either path: occupied slots (incl.
        # tombstones) must match the flat kernel slot-for-slot.
        assert occupied_rows(mtb.flat_view(block), 0) == \
            occupied_rows(flat, 0), (shape, start)
    assert 1 in branches, (shape, "incremental branch never exercised")


def test_deferred_zamboni_fires_on_tomb_pressure():
    """Tombstone drops stay OFF the hot tick until blk_tomb pressure
    crosses the threshold — then the full branch fires at the window
    and actually drops (count shrinks), matching rebalance() bit-exactly
    (exercised with an advancing MSN, unlike the fuzz's zero window)."""
    # Alternating head-insert / head-remove waves. The LIGHT remove
    # waves (below the pressure threshold of nb*bk/TOMB_PRESSURE_DEN =
    # 64 tombstones) leave tombstones aboard when the next insert wave
    # arms the danger trigger — the spill must ride them through
    # untouched (deferred). The final HEAVY wave (70 > 64) crosses the
    # pressure threshold while no danger fires, so the next insert
    # wave's first fire takes the full branch and the zamboni drops.
    ops = []
    seq = 0

    def insert_wave(n):
        nonlocal seq
        for _ in range(n):
            seq += 1
            ops.append(dict(kind=mtk.MT_INSERT, pos=0, seq=seq,
                            ref_seq=seq - 1, client=0,
                            pool_start=seq, text_len=1))

    def remove_wave(n):
        nonlocal seq
        for _ in range(n):
            seq += 1
            ops.append(dict(kind=mtk.MT_REMOVE, pos=0, end=1, seq=seq,
                            ref_seq=seq - 1, client=0))

    insert_wave(80)
    remove_wave(40)
    insert_wave(80)
    remove_wave(50)
    insert_wave(60)
    remove_wave(70)
    insert_wave(40)
    block = mtb.init_state(1, num_blocks=4, block_slots=64)
    k = 10
    saw_pressure_drop = False
    saw_deferred = False
    for start in range(0, len(ops), k):
        batch = mtk.make_merge_op_batch([ops[start:start + k]], 1, k)
        block, ovf = mtb.apply_tick_blocks(block, batch)
        assert int(np.asarray(ovf)[0]) == int(mtb.OVF_NONE), start
        ms = jnp.asarray([start], jnp.int32)  # advancing collab window
        branch = _decide(block, k)
        tomb_heavy = (int(np.asarray(block.blk_tomb).sum())
                      * mtb.TOMB_PRESSURE_DEN >= 4 * 64)
        if branch == 1 and int(np.asarray(block.blk_tomb).sum()) > 0:
            saw_deferred = True  # drops stayed off this hot tick
        if branch == 2:
            ref = mtb.rebalance(block, ms)
            nxt, _rs = mtb.maybe_rebalance_stats(block, ms, k)
            for f in mtb.BlockMergeState._fields:
                assert np.array_equal(np.asarray(getattr(nxt, f)),
                                      np.asarray(getattr(ref, f))), f
            if tomb_heavy and (int(np.asarray(nxt.count)[0])
                               < int(np.asarray(block.count)[0])):
                saw_pressure_drop = True
            block = nxt
        else:
            before = int(np.asarray(block.count)[0])
            block, _rs = mtb.maybe_rebalance_stats(block, ms, k)
            # The incremental/no-op branches NEVER drop.
            assert int(np.asarray(block.count)[0]) == before, start
    assert saw_deferred, "tombstones never rode through a hot-tick spill"
    assert saw_pressure_drop, "pressure-triggered zamboni never dropped"


def test_choose_block_geometry_head_fraction():
    """head_fraction=0 is the historical geometry bit-for-bit; higher
    observed concentration grows Bk monotonically (lane multiple, total
    capacity still admits min_slots) so the hot block absorbs more
    ticks per spill."""
    for slots, k in ((512, 32), (2048, 32), (8192, 32), (8192, 128)):
        base = mtb.choose_block_geometry(slots, k)
        assert base == mtb.choose_block_geometry(slots, k, 0.0)
        prev_bk = 0
        for hf in (0.0, 0.3, 0.6, 1.0):
            nb, bk = mtb.choose_block_geometry(slots, k, hf)
            assert bk % 128 == 0 and bk >= prev_bk
            prev_bk = bk
            worst = 2 * k + 8
            # Usable capacity (below the per-block worst-case reserve)
            # admits min_slots at every head_fraction.
            assert nb * (bk - worst) >= slots, (slots, k, hf, nb, bk)
        nb1, bk1 = mtb.choose_block_geometry(slots, k, 1.0)
        if slots >= 2048:
            assert bk1 > base[1], (slots, k)


def test_overflow_is_atomic_and_replayable():
    """Force a block overflow (tiny Bk, one-position insert storm): the
    kernel reports the first failed op index, the table is frozen at the
    pre-overflow frontier, and replaying the tail through the FLAT
    kernel (the host's fallback) converges to the flat-only result."""
    n_ops = 24
    ops = [dict(kind=mtk.MT_INSERT, pos=0, seq=s, ref_seq=s - 1, client=0,
                pool_start=s * 4, text_len=2)
           for s in range(1, n_ops + 1)]
    batch = mtk.make_merge_op_batch([ops], 1, n_ops)
    block = mtb.init_state(1, num_blocks=4, block_slots=4)
    block, ovf = mtb.apply_tick_blocks(block, batch)
    idx = int(np.asarray(ovf)[0])
    assert 0 < idx < n_ops  # overflowed mid-tick
    assert int(np.asarray(block.count)[0]) == idx  # frontier exact

    # Host fallback: pack the frozen table into a flat row and replay.
    packed = mtb.to_flat(block, slots=128)
    replay = mtk.make_merge_op_batch([ops[idx:]], 1, n_ops - idx)
    replayed = mtk.apply_tick(packed, replay)

    flat_only = mtk.apply_tick(mtk.init_state(1, 128), batch)
    assert occupied_rows(replayed, 0) == occupied_rows(flat_only, 0)


def test_block_to_sharded_conversion():
    """Sequence-parallel compatibility: a document leaving the block
    path for a sharded pool converts via from_block_state, and the
    sharded kernel continues the stream producing the same document as
    the block kernel continuing in place."""
    import jax

    from fluidframework_tpu.ops import mergetree_sharded as mts

    rng = random.Random(77)
    history = gen_stream(rng, 32)
    block, ovf = mtb.apply_tick_blocks(
        mtb.init_state(1, num_blocks=4, block_slots=64),
        mtk.make_merge_op_batch([history], 1, 32))
    assert int(np.asarray(ovf)[0]) == int(mtb.OVF_NONE)

    future = [dict(kind=mtk.MT_INSERT, pos=0, seq=33 + i, ref_seq=32 + i,
                   client=0, pool_start=1000 + 2 * i, text_len=2)
              for i in range(8)]
    batch = mtk.make_merge_op_batch([future], 1, 8)

    flat = mts.from_block_state(block, slots=128)
    mesh = mts.make_seg_mesh(jax.devices()[:8])
    sharded = mts.apply_tick_sharded(
        mts.shard_merge_state(flat, mesh), batch, mesh)
    block2, ovf = mtb.apply_tick_blocks(block, batch)
    assert int(np.asarray(ovf)[0]) == int(mtb.OVF_NONE)
    assert occupied_rows(sharded, 0) == \
        occupied_rows(mtb.flat_view(block2), 0)


def test_converters_roundtrip():
    """flat_view / from_flat / host_block_row agree with each other."""
    rng = random.Random(42)
    stream = gen_stream(rng, 40)
    flat = mtk.apply_tick(mtk.init_state(1, 256, num_props=2),
                          mtk.make_merge_op_batch([stream], 1, 40))
    packed = mtk.compact(flat, jnp.asarray([-1], np.int32))
    block = mtb.from_flat(packed, num_blocks=8)
    rebuilt = mtb.recompute_summaries(block)
    for f in ("blk_live_len", "blk_max_seq", "blk_tomb", "count"):
        assert np.array_equal(np.asarray(getattr(block, f)),
                              np.asarray(getattr(rebuilt, f))), f
    assert occupied_rows(mtb.flat_view(block), 0) == \
        occupied_rows(packed, 0)

    arrays = {f: np.asarray(getattr(packed, f)[0])
              for f in mtk.MergeState._fields}
    host = mtb.host_block_row(arrays, num_blocks=8, block_slots=32)
    for f in ("blk_count", "blk_live_len", "blk_max_seq", "blk_tomb"):
        assert np.array_equal(host[f], np.asarray(getattr(block, f)[0])), f
    for f in ("length", "ins_seq", "rem_seq", "pool_start"):
        assert np.array_equal(host[f], np.asarray(getattr(block, f)[0])), f


def test_serve_tick_blocks_best_composes_maintenance():
    """The serving-path composition the Pallas module exports (best
    apply + the conditional maintenance ladder) is bit-identical to
    calling the two legs explicitly — the fused shape storm._mixed_tick
    uses, kept honest on every backend."""
    from fluidframework_tpu.ops import mergetree_blocks_pallas as mtbp

    rng = random.Random(99)
    stream = gen_head_stream(rng, 48)
    k = 8
    a = mtb.init_state(1, num_blocks=8, block_slots=64, num_props=2)
    b = mtb.init_state(1, num_blocks=8, block_slots=64, num_props=2)
    zero = jnp.zeros((1,), jnp.int32)
    for start in range(0, 48, k):
        batch = mtk.make_merge_op_batch([stream[start:start + k]], 1, k)
        a, ovf_a, rs_a = mtbp.serve_tick_blocks_best(a, batch, zero, k)
        b, ovf_b = mtbp.apply_tick_blocks_best(b, batch)
        b, rs_b = mtb.maybe_rebalance_stats(b, zero, k)
        assert np.array_equal(np.asarray(ovf_a), np.asarray(ovf_b))
        assert np.array_equal(np.asarray(rs_a), np.asarray(rs_b))
        for f in mtb.BlockMergeState._fields:
            assert np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f))), (start, f)
