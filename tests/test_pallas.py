"""Pallas kernel tests (interpret mode on the CPU backend)."""

import numpy as np
import pytest

from pilosa_tpu.ops import pallas_kernels as pk

RNG = np.random.default_rng(21)
W = 1024  # small lane count for interpret-mode speed (multiple of 128)


def test_intersect_count_matches_numpy():
    for s in (1, 8, 16):
        a = RNG.integers(0, 2**32, size=(s, W), dtype=np.uint32)
        b = RNG.integers(0, 2**32, size=(s, W), dtype=np.uint32)
        got = np.asarray(pk.intersect_count(a, b))
        expect = np.bitwise_count(a & b).sum(axis=1).astype(np.int32)
        np.testing.assert_array_equal(got, expect)


def test_program_count_nested():
    leaves = RNG.integers(0, 2**32, size=(3, 8, W), dtype=np.uint32)
    prog = ("andnot", ("or", ("leaf", 0), ("leaf", 1)), ("leaf", 2))
    got = np.asarray(pk.program_count(leaves, prog))
    ref = (leaves[0] | leaves[1]) & ~leaves[2]
    expect = np.bitwise_count(ref).sum(axis=1).astype(np.int32)
    np.testing.assert_array_equal(got, expect)


def test_program_count_not_with_shard_padding():
    """Not-rooted programs complement the zero padding to all-ones; the
    padded shards' counts must be sliced off, never summed in."""
    for s in (3, 5):  # forces _pad_shards
        leaves = RNG.integers(0, 2**32, size=(1, s, W), dtype=np.uint32)
        got = np.asarray(pk.program_count(leaves, ("not", ("leaf", 0))))
        assert got.shape == (s,)
        expect = np.bitwise_count(~leaves[0]).sum(axis=1).astype(np.int32)
        np.testing.assert_array_equal(got, expect)


def test_pair_stream_counts_matches_numpy():
    """Scalar-prefetch query stream: data-dependent row gathers via
    PrefetchScalarGridSpec, per-query accumulation over shard blocks."""
    import jax.numpy as jnp

    for s in (3, 16):  # non-multiple of SHARD_BLOCK exercises blk=1
        rows = RNG.integers(0, 2**32, size=(5, s, W), dtype=np.uint32)
        ii = np.array([0, 4, 2, 2], dtype=np.int32)
        jj = np.array([1, 4, 0, 3], dtype=np.int32)
        got = np.asarray(pk.pair_stream_counts(
            jnp.asarray(rows), jnp.asarray(ii), jnp.asarray(jj)))
        expect = np.array([np.bitwise_count(rows[i] & rows[j]).sum()
                           for i, j in zip(ii, jj)], dtype=np.int32)
        np.testing.assert_array_equal(got, expect)


def test_cross_count_matrix_matches_numpy():
    """Blocked GroupBy cross-count kernel: counts[P, R] over ragged shapes
    that force prefix/row/word padding in every combination."""
    for p, r, w in ((1, 1, 512), (5, 7, 512), (8, 128, 1024), (9, 130, 512)):
        a = RNG.integers(0, 2**32, size=(p, w), dtype=np.uint32)
        b = RNG.integers(0, 2**32, size=(r, w), dtype=np.uint32)
        got = np.asarray(pk.cross_count_matrix(a, b))
        expect = np.bitwise_count(
            a[:, None, :] & b[None, :, :]).sum(axis=-1).astype(np.int32)
        np.testing.assert_array_equal(got, expect)


def test_cross_count_matrix_parity_with_xla():
    """PILOSA_TPU_PALLAS routes GroupBy levels through this kernel; it must
    agree with the XLA fused form on [*, S, W] slab operands."""
    from pilosa_tpu.ops.bitvector import cross_count_matrix as xla_ccm

    pref = RNG.integers(0, 2**32, size=(6, 3, 512), dtype=np.uint32)
    axis = RNG.integers(0, 2**32, size=(9, 3, 512), dtype=np.uint32)
    np.testing.assert_array_equal(np.asarray(pk.cross_count_matrix(pref, axis)),
                                  np.asarray(xla_ccm(pref, axis)))


def test_groupby_chunk_live_parity():
    """Full chunk contract (gather + AND + cross count + on-device prune):
    the shared composition with the Pallas kernel plugged in as cross_fn
    returns identical (n_live, indices, counts) to the XLA form."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitvector as bv

    slab_a = jnp.asarray(
        RNG.integers(0, 2**32, size=(5, 2, 512), dtype=np.uint32))
    slab_b = jnp.asarray(
        RNG.integers(0, 2**32, size=(4, 2, 512), dtype=np.uint32))
    idx = (jnp.asarray(np.array([0, 3, 4, 0], dtype=np.int32)),
           jnp.asarray(np.array([2, 0, 1, 0], dtype=np.int32)))
    args = ((slab_a, slab_b), idx, slab_b, jnp.int32(3), 32)
    got = jax.device_get(
        bv.groupby_chunk_live(*args, cross_fn=pk.cross_count_matrix))
    expect = jax.device_get(bv.groupby_chunk_live(*args))
    for g, e in zip(got, expect):
        np.testing.assert_array_equal(g, e)


def test_interpret_is_decided_per_platform(monkeypatch):
    """cpu interprets (tier-1), tpu compiles, anything else is refused —
    never silently interpreted."""
    import jax

    assert pk._interpret() is True  # conftest: cpu backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pk._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        pk._interpret()


def test_program_block_follows_arity():
    """The shard block shrinks with the leaf count so double-buffered
    operands stay inside the VMEM budget (4+ full-width leaves at a fixed
    16-deep block overflow the 16 MiB scoped limit on a v5e); past the
    8-sublane floor the word axis splits."""
    w = 32768
    assert pk._program_block(2, w) == (16, w)
    assert pk._program_block(4, w) == (8, w)
    assert pk._program_block(6, w) == (8, w)
    assert pk._program_block(8, w) == (8, w // 2)
    for n in range(1, 33):
        blk_s, blk_w = pk._program_block(n, w)
        assert n * blk_s * blk_w * 4 * 2 <= pk._VMEM_OPERAND_BUDGET
        assert blk_s % 8 == 0 and blk_w % 128 == 0 and w % blk_w == 0
    with pytest.raises(ValueError):
        pk._program_block(1 << 20, 128)


def test_program_count_word_split_accumulates(monkeypatch):
    """A budget small enough to split the word axis exercises the
    accumulate-over-word-blocks path at interpret-mode size."""
    monkeypatch.setattr(pk, "_VMEM_OPERAND_BUDGET", 3 * 8 * 256 * 4 * 2)
    assert pk._program_block(3, W) == (8, 256)
    leaves = RNG.integers(0, 2**32, size=(3, 11, W), dtype=np.uint32)
    prog = ("xor", ("and", ("leaf", 0), ("leaf", 1)), ("not", ("leaf", 2)))
    got = np.asarray(pk.program_count(tuple(leaves), prog))
    ref = (leaves[0] & leaves[1]) ^ ~leaves[2]
    np.testing.assert_array_equal(
        got, np.bitwise_count(ref).sum(axis=1).astype(np.int32))


# -- mesh composition (shard_map wrappers; interpret mode on the 8-device
#    CPU mesh: PILOSA_TPU_PALLAS must compose with multi-device)


@pytest.mark.parametrize("replicas", [1, 2])
def test_program_count_mesh_parity(replicas):
    import jax

    from pilosa_tpu.parallel.mesh import DeviceRunner, eval_count_total, make_mesh

    mesh = make_mesh(replicas=replicas)
    runner = DeviceRunner(mesh, use_pallas=True)
    assert runner.use_pallas  # no longer forced off under a mesh
    rng = np.random.default_rng(17)
    host = [rng.integers(0, 2**32, size=(5, 256), dtype=np.uint32)
            for _ in range(3)]
    leaves = [runner.put_leaf(h) for h in host]
    program = ("andnot", ("or", ("leaf", 0), ("leaf", 1)), ("leaf", 2))
    got = runner.count_total_leaves(leaves, program)
    expect = int(np.bitwise_count((host[0] | host[1]) & ~host[2]).sum())
    assert got == expect
    # and parity with the XLA mesh path on the same device arrays
    assert got == int(eval_count_total(tuple(leaves), program))


@pytest.mark.parametrize("replicas", [1, 2])
def test_groupby_chunk_mesh_pallas_parity(replicas):
    """GroupBy level chunks under PILOSA_TPU_PALLAS on a mesh: the blocked
    kernel runs per-device inside shard_map with an ICI psum over the shard
    axis, and must agree with the XLA mesh path and a numpy oracle."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.parallel.mesh import DeviceRunner, make_mesh

    mesh = make_mesh(replicas=replicas)
    xla = DeviceRunner(mesh, use_pallas=False)
    pallas = DeviceRunner(mesh, use_pallas=True)
    assert pallas.use_pallas
    rng = np.random.default_rng(23)
    host_a = rng.integers(0, 2**32, size=(6, 4, 512), dtype=np.uint32)
    host_b = rng.integers(0, 2**32, size=(5, 4, 512), dtype=np.uint32)
    idx = (jnp.asarray(np.array([0, 2, 5, 0], dtype=np.int32)),)
    n_valid, bound = jnp.int32(3), 30
    outs = []
    for runner in (xla, pallas):
        slab_a = runner.put_plane_slab(host_a)
        slab_b = runner.put_plane_slab(host_b)
        outs.append(jax.device_get(runner.groupby_chunk(
            (slab_a,), idx, slab_b, n_valid, bound)))
    for g, e in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(g, e)
    cmat = np.bitwise_count(
        host_a[np.asarray(idx[0][:3])][:, None] & host_b[None]).reshape(
            3, 5, -1).sum(axis=-1)
    lp, lr = np.nonzero(cmat)
    n_live, flat_idx, counts = outs[1]
    assert int(n_live) == lp.size
    np.testing.assert_array_equal(flat_idx[:lp.size] // 5, lp)
    np.testing.assert_array_equal(counts[:lp.size], cmat[lp, lr])


def test_executor_groupby_pallas_parity(tmp_path):
    """End to end: PILOSA_TPU_PALLAS GroupBy through the executor matches
    the XLA path's groups, still at one host sync per level."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models import Holder
    from pilosa_tpu.parallel.mesh import DeviceRunner

    rng = np.random.default_rng(27)
    results = {}
    for mode, use_pallas in (("xla", False), ("pallas", True)):
        h = Holder(str(tmp_path / mode)).open()
        ex = Executor(h, runner=DeviceRunner(use_pallas=use_pallas))
        idx = h.create_index("gp", track_existence=False)
        rng = np.random.default_rng(27)  # identical data both runs
        for fname in ("a", "b"):
            f = idx.create_field(fname)
            rids, cids = [], []
            for r in range(8):
                cols = rng.choice(2000, size=120, replace=False)
                rids += [r] * len(cols)
                cids += [int(c) for c in cols]
            f.import_bits(rids, cids)
        before = ex.groupby_host_syncs
        (groups,) = ex.execute("gp", "GroupBy(Rows(field=a), Rows(field=b))")
        assert ex.groupby_host_syncs - before == 1
        results[mode] = list(groups)
        h.close()
    assert results["pallas"] == results["xla"]


@pytest.mark.parametrize("replicas", [1, 2])
def test_pair_stream_counts_mesh_parity(replicas):
    import jax

    from pilosa_tpu.parallel.mesh import DeviceRunner, make_mesh

    mesh = make_mesh(replicas=replicas)
    runner = DeviceRunner(mesh)
    rng = np.random.default_rng(19)
    host = rng.integers(0, 2**32, size=(6, 4, 256), dtype=np.uint32)
    rows = runner.put_plane_slab(host)  # [R, S(padded), W] sharded
    k = 10
    ii = rng.integers(0, 6, size=k).astype(np.int32)
    jj = rng.integers(0, 6, size=k).astype(np.int32)
    got = pk.pair_stream_counts_mesh(mesh, rows, ii, jj)
    for q in range(k):
        expect = int(np.bitwise_count(host[ii[q]] & host[jj[q]]).sum())
        assert got[q] == expect, (q, got[q], expect)


# -- run-container PR kernels (ISSUE 17): fused TopN counts, BSI sweeps


def test_topn_counts_packed_parity():
    """Packed [3, R] = (|row∩src|, |row|, |src|) against numpy and the
    XLA twin, across shapes that force row AND word padding."""
    from pilosa_tpu.ops.topn import tanimoto_counts_packed as xla_packed

    for r, w in ((1, 512), (8, 2048), (100, 2048), (130, 4096)):
        rows = RNG.integers(0, 2**32, size=(r, w), dtype=np.uint32)
        src = RNG.integers(0, 2**32, size=(w,), dtype=np.uint32)
        got = np.asarray(pk.topn_counts_packed(rows, src))
        assert got.shape == (3, r)
        np.testing.assert_array_equal(
            got[0], np.bitwise_count(rows & src).sum(axis=1))
        np.testing.assert_array_equal(
            got[1], np.bitwise_count(rows).sum(axis=1))
        assert np.all(got[2] == np.bitwise_count(src).sum())
        np.testing.assert_array_equal(got, np.asarray(xla_packed(rows, src)))


def test_top_rows_pallas_matches_xla():
    from pilosa_tpu.ops.topn import top_rows as xla_top_rows

    rows = RNG.integers(0, 2**32, size=(12, 512), dtype=np.uint32)
    for k in (1, 5, 50):
        gc, gi = pk.top_rows(rows, k)
        ec, ei = xla_top_rows(rows, k)
        np.testing.assert_array_equal(np.asarray(gc), np.asarray(ec))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(ei))


def test_bsi_compare_all_ops_parity():
    """Blocked VMEM sweep vs the XLA unrolled form: every op, values that
    exercise strict/equal boundaries, ragged shard/word padding."""
    from pilosa_tpu.ops import bsi as bsiops

    depth, s, w = 6, 3, 640  # pads S->8 and W->1024
    rng = np.random.default_rng(11)
    vals = rng.integers(0, 2**depth, size=(s, w * 32), dtype=np.int64)
    planes = np.stack([
        np.packbits(((vals >> i) & 1).astype(np.uint8), axis=-1,
                    bitorder="little").view(np.uint32).reshape(s, w)
        for i in range(depth)]).astype(np.uint32)
    exists = np.full((s, w), 0xFFFFFFFF, dtype=np.uint32)
    for op in ("lt", "lte", "gt", "gte", "eq", "neq"):
        for pred in (0, 1, 17, 2**depth - 1):
            bits = bsiops.value_to_bits(pred, depth)
            got = np.asarray(pk.bsi_compare(planes, exists, bits, op))
            expect = np.asarray(bsiops.compare(planes, exists, bits, op))
            np.testing.assert_array_equal(got, expect, err_msg=f"{op} {pred}")


def test_bsi_compare_respects_exists():
    """Columns outside the existence row never match, whatever the op."""
    from pilosa_tpu.ops import bsi as bsiops

    depth, s, w = 4, 2, 512
    planes = RNG.integers(0, 2**32, size=(depth, s, w), dtype=np.uint32)
    exists = RNG.integers(0, 2**32, size=(s, w), dtype=np.uint32)
    bits = bsiops.value_to_bits(5, depth)
    for op in ("lt", "gte", "neq"):
        got = np.asarray(pk.bsi_compare(planes, exists, bits, op))
        assert not np.any(got & ~exists)
        np.testing.assert_array_equal(
            got, np.asarray(bsiops.compare(planes, exists, bits, op)))


def test_bsi_sum_counts_parity():
    """Packed [depth+1, S] per-plane counts + filter count in one kernel
    vs the XLA sum_counts row layout."""
    from pilosa_tpu.ops import bsi as bsiops

    for depth, s, w in ((1, 1, 512), (8, 3, 640), (24, 9, 512)):
        planes = RNG.integers(0, 2**32, size=(depth, s, w), dtype=np.uint32)
        filt = RNG.integers(0, 2**32, size=(s, w), dtype=np.uint32)
        got = np.asarray(pk.bsi_sum_counts(planes, filt))
        expect = np.asarray(bsiops.sum_counts(planes, filt))
        np.testing.assert_array_equal(got, expect)


def test_bsi_sum_counts_depth_cap():
    planes = RNG.integers(0, 2**32, size=(128, 1, 512), dtype=np.uint32)
    filt = RNG.integers(0, 2**32, size=(1, 512), dtype=np.uint32)
    with pytest.raises(ValueError):
        pk.bsi_sum_counts(planes, filt)
