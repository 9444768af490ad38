"""Checkpoint serializer/manager + migration engine: roundtrips, size
accounting (the feasibility model's S_j), compression ratios, elastic
restore, end-to-end migration."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, serialize_tree, deserialize_tree, tree_bytes
from repro.checkpoint.serializer import from_bytes, read, to_bytes
from repro.core import feasibility as fz
from repro.core.migration import migrate_job


def make_tree(seed=0, scale=1.0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    return {
        "w": jax.random.normal(ks[0], (128, 64), jnp.float32) * scale,
        "b": jax.random.normal(ks[1], (64,), jnp.float32),
        "emb": {"table": jax.random.normal(ks[2], (1000, 32), jnp.bfloat16)},
        "step": jnp.int32(7),
    }


def column_major(x):
    """``x`` kept column-major on its device, as a TPU train step keeps
    some of its outputs."""
    from jax.experimental.layout import Format, Layout

    return jax.device_put(x, Format(Layout((1, 0)), x.sharding))


def odd_tree(seed=0, shift=0.0):
    """Leaves the writer has to take as they come: bfloat16, a 0-d int32
    step, a zero-size leaf, a transposed and a strided numpy view, and a
    device array in a column-major layout."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {
        "col": column_major(jax.random.normal(ks[4], (24, 40), jnp.float32) + shift),
        "w": jax.random.normal(ks[0], (130, 64), jnp.float32) + shift,
        "emb": {"table": jax.random.normal(ks[1], (300, 32), jnp.bfloat16) + shift},
        "empty": np.zeros((0, 4), np.float32),
        "wT": (np.asarray(jax.random.normal(ks[2], (40, 24))) + shift).T,
        "strided": (np.asarray(jax.random.normal(ks[3], (50, 6))) + shift)[::2, 1:5],
        "step": np.int32(7),
    }


@pytest.mark.parametrize("mode", ["full", "int8", "delta-int8"])
def test_saved_file_is_the_format_and_restores_owned_leaves(tmp_path, mode):
    """The file a save streams is the in-memory encoding byte for byte,
    its size is S_j, and a restore gives arrays of their own: bit-equal
    where the entry is raw, within the int8 bound where it is not."""
    base, tree = odd_tree(0), odd_tree(0, shift=0.01)
    assert not tree["wT"].flags.c_contiguous and not tree["strided"].flags.c_contiguous
    assert tree["col"].format.layout.major_to_minor == (1, 0)
    mgr = CheckpointManager(str(tmp_path), job="fmt", mode=mode)
    mgr.save(1, base)
    info = mgr.save(2, tree)  # delta-int8 against the first save
    delta_base = base if mode == "delta-int8" else None
    with open(info.path, "rb") as f:
        got = f.read()
    assert got == to_bytes(serialize_tree(tree, mode=mode, base=delta_base))
    assert info.nbytes == len(got) == os.path.getsize(info.path)
    back, _ = mgr.restore(tree, base=delta_base)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    again = deserialize_tree(from_bytes(got), tree, base=delta_base)
    for x, b, y, z in zip(jax.tree.leaves(tree), jax.tree.leaves(base),
                          jax.tree.leaves(back), jax.tree.leaves(again)):
        x = np.asarray(x)
        assert y.shape == x.shape and y.dtype == x.dtype
        assert y.flags.writeable and y.flags.c_contiguous and y.flags.owndata
        np.testing.assert_array_equal(y, z)
        if mode == "full" or not jnp.issubdtype(x.dtype, jnp.floating):
            np.testing.assert_array_equal(y, x)
        elif x.size:
            x32, y32 = x.astype(np.float32), y.astype(np.float32)
            ref = np.asarray(b, np.float32) if mode == "delta-int8" else 0.0
            bound = (np.max(np.abs(x32 - ref)) / 127
                     + float(jnp.finfo(x.dtype).eps) * np.abs(x32))
            assert np.all(np.abs(y32 - x32) <= bound)


@pytest.mark.parametrize("mode,cut", [("full", "magic"), ("full", "manifest"),
                                      ("full", "payload"), ("int8", "payload")])
def test_a_truncated_checkpoint_raises(tmp_path, mode, cut):
    mgr = CheckpointManager(str(tmp_path), job="cut", mode=mode)
    info = mgr.save(1, odd_tree())
    with open(info.path, "rb") as f:
        raw = f.read()
    mlen = int.from_bytes(raw[8:16], "little")
    keep = {"magic": 5, "manifest": 16 + mlen // 2, "payload": len(raw) - 3}[cut]
    with open(info.path, "wb") as f:
        f.write(raw[:keep])
    with pytest.raises(ValueError, match="checkpoint"):
        mgr.restore(odd_tree())
    with open(info.path, "rb") as f, pytest.raises(ValueError, match="checkpoint"):
        read(f)


def test_full_roundtrip_exact():
    tree = make_tree()
    payload = serialize_tree(tree, mode="full")
    back = deserialize_tree(payload, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bytes_roundtrip():
    tree = make_tree()
    payload = serialize_tree(tree, mode="full")
    again = from_bytes(to_bytes(payload))
    assert again.manifest == payload.manifest
    assert again.data == payload.data


def test_int8_compresses_and_bounded_error():
    tree = make_tree()
    raw = tree_bytes(tree)
    payload = serialize_tree(tree, mode="int8")
    # f32 leaves shrink ~4x; bf16 ~2x; int leaves stay raw
    assert len(payload.data) < 0.45 * raw
    back = deserialize_tree(payload, tree)
    err = float(jnp.max(jnp.abs(back["w"] - tree["w"])))
    amax = float(jnp.max(jnp.abs(tree["w"])))
    assert err <= amax / 127
    np.testing.assert_array_equal(np.asarray(back["step"]), np.asarray(tree["step"]))


def test_delta_int8_roundtrip():
    base = make_tree(0)
    stepped = jax.tree.map(
        lambda x: x + 0.01 if jnp.issubdtype(x.dtype, jnp.floating) else x, base
    )
    payload = serialize_tree(stepped, mode="delta-int8", base=base)
    back = deserialize_tree(payload, stepped, base=base)
    err = float(jnp.max(jnp.abs(back["w"] - stepped["w"])))
    assert err < 1e-3  # delta range is tiny -> tiny quant error


def test_manager_save_restore_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), job="j1", keep=2)
    tree = make_tree()
    for step in (10, 20, 30):
        mgr.save(step, tree)
    assert len(mgr._history) == 2  # retention
    assert mgr.latest.step == 30
    assert mgr.latest_bytes > 0
    back, info = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))
    assert info.step == 30


def test_manager_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), job="j2", async_save=True)
    tree = make_tree()
    mgr.save(1, tree)
    mgr.wait()
    assert mgr.latest_bytes > 0
    back, _ = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))


def test_measured_size_feeds_feasibility(tmp_path):
    """The orchestrator's S_j is the measured serialized size."""
    mgr = CheckpointManager(str(tmp_path), job="j3")
    tree = make_tree()
    mgr.save(1, tree)
    S = mgr.latest_bytes
    assert abs(S - tree_bytes(tree)) / tree_bytes(tree) < 0.1  # manifest overhead only
    v = fz.evaluate(S, 10e9, 2.5 * 3600)
    assert bool(v.feasible)  # tiny tree: class A


def test_migration_end_to_end(tmp_path):
    """save -> WAN model -> import at destination -> restore: identical
    state, report terms match eq. (1)."""
    src_root, dst_root = str(tmp_path / "siteA"), str(tmp_path / "siteB")
    mgr = CheckpointManager(src_root, job="trainjob")
    tree = make_tree()
    mgr.save(42, tree)
    dst, report = migrate_job(mgr, dst_root, bandwidth_bps=1e9, window_s=2.5 * 3600)
    assert report.step == 42
    assert report.workload_class == 0
    assert report.feasible_in_window is True
    assert report.t_transfer_s == pytest.approx(8 * report.nbytes / 1e9, rel=1e-6)
    assert report.t_cost_s == pytest.approx(
        report.t_transfer_s + fz.T_LOAD_S + fz.T_DOWNTIME_S, rel=1e-6
    )
    back, _ = dst.restore(tree)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))


def test_elastic_restore_with_shardings(tmp_path):
    """Restore places leaves onto a new mesh (migration to a different
    slice)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    mgr = CheckpointManager(str(tmp_path), job="j4")
    tree = make_tree()
    mgr.save(1, tree)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    back, _ = mgr.restore(tree, shardings=sh)
    assert all(x.sharding == NamedSharding(mesh, P()) for x in jax.tree.leaves(back))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_int8_roundtrip_at_odd_leaf_sizes(monkeypatch, impl):
    """int8 checkpoints of leaves whose 256-blocks are no whole number of
    the quantize kernel's row tiles (100 rows, a ragged tail, a single
    short block) round-trip within the int8 bound, through the Pallas
    kernels (interpret mode) as through the jnp oracle."""
    monkeypatch.setenv("REPRO_QUANT_IMPL", impl)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    tree = {
        "rows100": jax.random.normal(ks[0], (100, 256), jnp.float32),
        "ragged": jax.random.normal(ks[1], (3, 5, 37), jnp.float32),
        "tiny": jax.random.normal(ks[2], (7,), jnp.float32),
    }
    back = deserialize_tree(serialize_tree(tree, mode="int8"), tree)
    for name, x in tree.items():
        assert back[name].shape == x.shape and back[name].dtype == x.dtype
        err = float(jnp.max(jnp.abs(back[name] - x)))
        assert err <= float(jnp.max(jnp.abs(x))) / 127, name
