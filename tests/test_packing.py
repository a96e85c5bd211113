"""Packed flat-buffer robust-aggregation engine (distributed/packing.py):
layout round-trips, BIT-exact agreement with the per-leaf oracle, the
one-collective-per-phase schedule, and the flat-stack entry point."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aragg import RobustAggregator
from repro.distributed import packing
from repro.distributed.packing import packed_aggregate, packer_for
from repro.distributed.robust_sync import robust_gradient_sync

BLOCK_D = 256  # small blocks so tiny test leaves still span multiple blocks


def _mixed_dtype_tree(key, W=6):
    ks = jax.random.split(key, 4)
    return {
        "w": jax.random.normal(ks[0], (W, 4, 6), jnp.float32),
        "b": jax.random.normal(ks[1], (W,), jnp.float32).astype(jnp.bfloat16),
        "e": jnp.zeros((W, 0, 3), jnp.float32),  # empty leaf
        "h": jax.random.normal(ks[2], (W, 513), jnp.float32).astype(jnp.float16),
        "s": {"v": jax.random.normal(ks[3], (W, 3, 2, 2), jnp.float32)},
    }


def _f32_tree(key, W=12, sizes=((24,), (300,), (7, 11), (1000,), (2, 0))):
    ks = jax.random.split(key, len(sizes))
    return {f"l{i}": jax.random.normal(k, (W,) + s, jnp.float32)
            for i, (k, s) in enumerate(zip(ks, sizes))}


# ------------------------------------------------------------------- layout
def test_pack_unpack_roundtrip_mixed_dtypes(key):
    tree = _mixed_dtype_tree(key)
    packer = packer_for(tree, block_d=BLOCK_D)
    buf = packer.pack(tree)
    assert buf.dtype == jnp.float32
    assert buf.shape == (6, packer.n_pad)
    assert packer.n_pad % BLOCK_D == 0
    # every leaf segment starts on a block boundary (bit-exactness alignment)
    assert all(off % BLOCK_D == 0 for off in packer.offsets)
    back = packer.unpack_stacked(buf)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # single-row unpack slices worker 0 exactly
    row = packer.unpack(buf[0])
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(row)):
        np.testing.assert_array_equal(np.asarray(a[0], np.float32),
                                      np.asarray(b, np.float32))


def _lane_tree(key, W=5):
    """Leaves whose minor dim is not a multiple of 128: lane-padded rows
    (200, 130), a dense narrow leaf (24), a bf16 leaf and a whole-tile one."""
    ks = jax.random.split(key, 5)
    return {
        "a": jax.random.normal(ks[0], (W, 3, 8, 200), jnp.float32),
        "b": jax.random.normal(ks[1], (W, 5, 24), jnp.float32),
        "c": jax.random.normal(ks[2], (W, 6, 130), jnp.float32).astype(jnp.bfloat16),
        "d": jax.random.normal(ks[3], (W, 9, 128), jnp.float32),
        "e": jax.random.normal(ks[4], (W, 300), jnp.float32).astype(jnp.float16),
    }


@pytest.mark.parametrize("for_kernels", [False, True])
@pytest.mark.parametrize("W", [1, 5, 12])
def test_lane_aligned_roundtrip(key, W, for_kernels):
    """Leaves whose minor dim is not a multiple of 128 come back exactly,
    through either writer of the buffer (XLA pieces, or the pack_rows
    kernel where the buffer feeds the kernels directly)."""
    tree = _lane_tree(key, W)
    packer = packer_for(tree, block_d=BLOCK_D)
    buf = packer.pack(tree, for_kernels=for_kernels)
    rows = packing.kernel_rows(W) if for_kernels else W
    assert buf.shape == (rows, packer.n_pad) and buf.dtype == jnp.float32
    assert not np.asarray(buf[W:]).any()  # padded rows are zero
    back = packer.unpack_stacked(buf[:W])
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    row = packer.unpack(buf[W - 1])
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(row)):
        np.testing.assert_array_equal(np.asarray(a[W - 1], np.float32),
                                      np.asarray(b, np.float32))


def test_segments_and_lane_rows_are_aligned(key):
    """Every segment starts on a block_d boundary; every row of a
    lane-padded leaf starts on a multiple of 128 and is followed by zeros
    up to its lane width."""
    W = 3
    tree = _lane_tree(key, W)
    packer = packer_for(tree, block_d=BLOCK_D)
    buf = np.asarray(packer.pack(tree))
    assert all(off % BLOCK_D == 0 for off in packer.offsets)
    assert packer.n_pad % BLOCK_D == 0
    padded = 0
    for leaf, shape, off in zip(jax.tree_util.tree_leaves(tree),
                                packer.leaf_shapes, packer.offsets):
        C, Cp = shape[-1], packing.lane_width(shape[-1])
        if C == Cp:
            continue
        padded += 1
        assert Cp % 128 == 0
        rows = np.asarray(leaf, np.float32).reshape(W, -1, C)
        for r in range(rows.shape[1]):
            start = off + r * Cp
            assert start % 128 == 0
            np.testing.assert_array_equal(buf[:, start:start + C], rows[:, r])
            assert not buf[:, start + C:start + Cp].any()
    assert padded == 3  # the 200-, 130- and 300-wide leaves; 24 stays dense


def test_lane_pad_cols_counter(key):
    """``sync_lane_pad_cols`` is the hand count of zero lanes: (256-200)
    lanes on 24 rows, (256-130) on 6 and (384-300) on the one row of the
    300-wide leaf; the 24- and 128-wide leaves add none."""
    tree = _lane_tree(key, W=4)
    packer = packer_for(tree, block_d=BLOCK_D)
    hand = 24 * (256 - 200) + 6 * (256 - 130) + (384 - 300)
    assert packer.lane_pad_cols == hand
    ra = RobustAggregator.from_spec("rfa", mixing="bucketing", s=2)
    _, info = robust_gradient_sync(tree, ra, key=key, engine="packed",
                                   block_d=BLOCK_D, telemetry=True)
    assert int(info["telemetry"]["sync_lane_pad_cols"]) == hand
    assert int(info["telemetry"]["sync_n_pad"]) == packer.n_pad


@pytest.mark.parametrize("use_kernels,expect", [(None, 8), (False, 5)])
def test_buffer_rows_on_a_trivial_mesh(key, monkeypatch, use_kernels, expect):
    """Where the kernels read the buffer directly, it is born with the
    kernels' 8-row multiple; the plain-jnp route keeps W rows."""
    seen = []
    orig = packing.reshard_in

    def record(buf, mesh):
        seen.append(buf.shape)
        return orig(buf, mesh)

    monkeypatch.setattr(packing, "reshard_in", record)
    tree = _f32_tree(key, W=5, sizes=((3, 200), (40,)))
    ra = RobustAggregator.from_spec("cm", mixing="bucketing", s=2)
    robust_gradient_sync(tree, ra, key=key, engine="packed", block_d=BLOCK_D,
                         use_kernels=use_kernels)
    assert [s[0] for s in seen] == [expect]


_MULTI_DEVICE_ROWS = """
import jax, jax.numpy as jnp
from repro.core.aragg import RobustAggregator
from repro.distributed import packing
from repro.distributed.robust_sync import robust_gradient_sync
from repro.launch.mesh import make_host_mesh
seen = []
orig = packing.reshard_in
packing.reshard_in = lambda buf, mesh: (seen.append(buf.shape), orig(buf, mesh))[1]
tree = {"a": jnp.ones((5, 3, 200)), "b": jnp.ones((5, 40))}
for rule in ("rfa", "cm"):
    ra = RobustAggregator.from_spec(rule, mixing="bucketing", s=2)
    jax.make_jaxpr(lambda t: robust_gradient_sync(
        t, ra, key=jax.random.PRNGKey(0), mesh=make_host_mesh(4, 2),
        engine="packed", block_d=256)[0])(tree)
print([s[0] for s in seen])
"""


def test_buffer_rows_on_a_multi_device_mesh():
    """On a forced 8-device mesh the buffer keeps W rows: the ingress
    all-to-all carries the messages and no padding rows."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _MULTI_DEVICE_ROWS], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "[5, 5]"


def test_packer_layout_is_cached(key):
    tree = _f32_tree(key)
    assert packer_for(tree, block_d=BLOCK_D) is packer_for(tree, block_d=BLOCK_D)
    assert packer_for(tree, block_d=BLOCK_D) is not packer_for(tree, block_d=512)


def test_packer_cache_distinct_for_dtype_and_block(key):
    """Trees that differ ONLY in a leaf dtype (or in block_d) must map to
    distinct cached layouts — dtype drives the unpack cast."""
    tree32 = {"a": jnp.zeros((4, 37), jnp.float32),
              "b": jnp.zeros((4, 5, 3), jnp.float32)}
    tree16 = {"a": tree32["a"], "b": tree32["b"].astype(jnp.bfloat16)}
    p32 = packer_for(tree32, block_d=BLOCK_D)
    p16 = packer_for(tree16, block_d=BLOCK_D)
    assert p32 is not p16
    assert p16.leaf_dtypes[1] == jnp.bfloat16
    assert packer_for(tree32, block_d=2 * BLOCK_D) is not p32
    # same shapes+dtypes+block -> the SAME object
    assert packer_for({k: v + 1 for k, v in tree32.items()},
                      block_d=BLOCK_D) is p32


def test_packer_built_once_across_syncs_in_one_trace(key, monkeypatch):
    """Two packed_robust_sync calls on the same tree structure inside ONE
    jit trace must hit the layout cache — GradPacker is built at most once
    (zero times if a previous test already cached this layout; use a unique
    shape so the first call builds)."""
    builds = {"n": 0}
    orig_init = packing.GradPacker.__init__

    def counting_init(self, *a, **kw):
        builds["n"] += 1
        orig_init(self, *a, **kw)

    monkeypatch.setattr(packing.GradPacker, "__init__", counting_init)
    tree = _f32_tree(key, W=5, sizes=((131,), (9, 3)))  # unique layout
    ra = RobustAggregator.from_spec("cm", mixing="bucketing", s=2)

    @jax.jit
    def two_syncs(t, k):
        o1, _ = packing.packed_robust_sync(t, ra, key=k, block_d=BLOCK_D)
        o2, _ = packing.packed_robust_sync(t, ra, key=k, block_d=BLOCK_D)
        return o1, o2

    two_syncs(tree, jax.random.PRNGKey(0))
    assert builds["n"] == 1


@pytest.mark.parametrize("engine", ["packed", "per_leaf"])
@pytest.mark.parametrize("use_kernels", [True, False])
def test_empty_leaf_through_both_engines(key, engine, use_kernels):
    """A zero-size leaf inside an otherwise normal tree must pass through
    both engines (guarded before any reshape/reshard) and come back as a
    zero array of the right trailing shape."""
    tree = {"a": jax.random.normal(key, (6, 40), jnp.float32),
            "empty": jnp.zeros((6, 2, 0), jnp.float32),
            "b": jax.random.normal(key, (6, 3, 5), jnp.float32)}
    for agg in ("rfa", "cm"):
        ra = RobustAggregator.from_spec(agg, mixing="bucketing", s=2)
        out, _ = robust_gradient_sync(tree, ra, key=jax.random.PRNGKey(1),
                                      engine=engine, block_d=BLOCK_D,
                                      use_kernels=use_kernels)
        assert out["empty"].shape == (2, 0)
        assert out["a"].shape == (40,) and out["b"].shape == (3, 5)
        assert np.all(np.isfinite(np.asarray(out["a"])))


def test_empty_tree_degenerate():
    tree = {"e": jnp.zeros((4, 0), jnp.float32)}
    ra = RobustAggregator.from_spec("rfa", mixing="none")
    out, _ = robust_gradient_sync(tree, ra, engine="packed", block_d=BLOCK_D)
    assert out["e"].shape == (0,)


# ----------------------------------------------- bit-exactness vs the oracle
RULES = [
    ("krum", {"n_byzantine": 2}),
    ("rfa", {}),
    ("cclip", {"tau": 3.0}),
    ("cm", {}),
    ("tm", {"n_trim": 2}),
    ("mean", {}),
]
MIXINGS = ["none", "bucketing", "resampling"]


@pytest.mark.parametrize("agg,kwargs", RULES, ids=[r[0] for r in RULES])
@pytest.mark.parametrize("mixing", MIXINGS)
def test_packed_bit_identical_to_per_leaf_oracle(key, agg, kwargs, mixing):
    """The packed engine performs the identical fp32 operation sequence as
    the per-leaf kernel oracle (leaf segments are block-aligned, the Gram
    kernel chains its accumulator), so outputs match BIT FOR BIT."""
    tree = _f32_tree(key)
    ra = RobustAggregator.from_spec(agg, mixing=mixing, s=3, **kwargs)
    agg_key = jax.random.PRNGKey(42)
    out_p, info_p = robust_gradient_sync(tree, ra, key=agg_key,
                                         engine="packed", block_d=BLOCK_D)
    out_o, info_o = robust_gradient_sync(tree, ra, key=agg_key,
                                         engine="per_leaf", block_d=BLOCK_D,
                                         use_kernels=True)
    for lp, lo in zip(jax.tree_util.tree_leaves(out_p),
                      jax.tree_util.tree_leaves(out_o)):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lo))
    if "agg_weights" in info_p:
        np.testing.assert_array_equal(np.asarray(info_p["agg_weights"]),
                                      np.asarray(info_o["agg_weights"]))


@pytest.mark.parametrize("agg,mixing", [
    ("krum", "bucketing"), ("rfa", "resampling"), ("cclip", "bucketing"),
    ("cm", "bucketing"),
])
def test_packed_matches_stacked_semantics(key, agg, mixing):
    """Against the original stacked RobustAggregator (value semantics)."""
    tree = _f32_tree(key)
    kwargs = {"n_byzantine": 2} if agg == "krum" else (
        {"tau": 3.0} if agg == "cclip" else {})
    ra = RobustAggregator.from_spec(agg, mixing=mixing, s=3, **kwargs)
    agg_key = jax.random.PRNGKey(7)
    out, _ = robust_gradient_sync(tree, ra, key=agg_key, engine="packed",
                                  block_d=BLOCK_D)
    flat_out = jnp.concatenate(
        [x.reshape(-1) for x in jax.tree_util.tree_leaves(out)]
    )
    leaves = jax.tree_util.tree_leaves(tree)
    stacked = jnp.concatenate([x.reshape(x.shape[0], -1) for x in leaves], axis=1)
    expect = ra(stacked, key=agg_key)
    np.testing.assert_allclose(flat_out, expect, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- collective schedule
@pytest.mark.parametrize("n_leaves", [3, 17])
@pytest.mark.parametrize("agg", ["rfa", "cm"])
def test_exactly_one_reshard_pair_per_sync(key, monkeypatch, agg, n_leaves):
    """One reshard-in and one reshard-out per sync, REGARDLESS of leaf count
    (the per-leaf path pays two collectives per leaf — the point of the
    packed engine)."""
    sizes = tuple((16 + i,) for i in range(n_leaves))
    tree = _f32_tree(key, W=8, sizes=sizes)
    calls = {"in": 0, "out": 0}
    orig_in, orig_out = packing.reshard_in, packing.reshard_out

    def count_in(buf, mesh):
        calls["in"] += 1
        return orig_in(buf, mesh)

    def count_out(vec, mesh):
        calls["out"] += 1
        return orig_out(vec, mesh)

    monkeypatch.setattr(packing, "reshard_in", count_in)
    monkeypatch.setattr(packing, "reshard_out", count_out)
    ra = RobustAggregator.from_spec(agg, mixing="bucketing", s=2)
    robust_gradient_sync(tree, ra, key=key, engine="packed", block_d=BLOCK_D)
    assert calls == {"in": 1, "out": 1}


# ------------------------------------------------------- telemetry contract
@pytest.mark.parametrize("agg,kwargs", [("rfa", {}), ("cm", {}),
                                        ("cclip", {"tau": 3.0})],
                         ids=["rfa", "cm", "cclip"])
def test_telemetry_off_is_bit_exact_on_is_close(key, agg, kwargs):
    """``telemetry=False`` (explicit) must execute the SEED program — output
    bit-identical to the default call AND to the per-leaf kernel oracle
    (the existing bit-exactness bar is untouched by the observability
    layer). ``telemetry=True`` may differ only at XLA-fusion level (~1 ulp)
    and must carry the metrics pytree in the info dict."""
    tree = _f32_tree(key)
    ra = RobustAggregator.from_spec(agg, mixing="bucketing", s=3, **kwargs)
    k = jax.random.PRNGKey(17)
    out_def, info_def = robust_gradient_sync(tree, ra, key=k, engine="packed",
                                             block_d=BLOCK_D)
    out_off, info_off = robust_gradient_sync(tree, ra, key=k, engine="packed",
                                             block_d=BLOCK_D, telemetry=False)
    out_oracle, _ = robust_gradient_sync(tree, ra, key=k, engine="per_leaf",
                                         block_d=BLOCK_D, use_kernels=True)
    assert "telemetry" not in info_def and "telemetry" not in info_off
    for a, b, c in zip(jax.tree_util.tree_leaves(out_off),
                       jax.tree_util.tree_leaves(out_def),
                       jax.tree_util.tree_leaves(out_oracle)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    out_on, info_on = robust_gradient_sync(tree, ra, key=k, engine="packed",
                                           block_d=BLOCK_D, telemetry=True)
    assert "telemetry" in info_on and info_on["telemetry"]
    for a, b in zip(jax.tree_util.tree_leaves(out_on),
                    jax.tree_util.tree_leaves(out_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------- flat-stack entry
def test_packed_aggregate_flat_stack(key):
    xs = jax.random.normal(key, (10, 700), jnp.float32)
    for agg, kwargs in [("rfa", {}), ("cm", {}), ("cclip", {"tau": 5.0})]:
        ra = RobustAggregator.from_spec(agg, mixing="bucketing", s=2, **kwargs)
        k = jax.random.PRNGKey(3)
        out = packed_aggregate(xs, ra, key=k, block_d=BLOCK_D)
        np.testing.assert_allclose(out, ra(xs, key=k), rtol=2e-4, atol=2e-4)
        assert out.shape == (700,)
