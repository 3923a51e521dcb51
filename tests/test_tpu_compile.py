"""Ask the TPU's compiler, without a TPU, whether the served path's
kernels and programs compile at the real 1B-column shapes.

The sandbox has no chip, but libtpu compiles for a chip that is
described and not attached (guide ``on-chip-measurement`` §2,
rehearsal 3). Interpret-mode tests cannot see what the chip's compiler
refuses (tiling, VMEM, HBM); these can. Nothing runs: a compile that
passes is not a chip run — ``chip_smoke.py`` is.

Shapes: 954 shards bucket to a 1024-row stack of one 2^20-bit shard row
each, ``uint32[1024, 32768]``; the int field is the smoke's ``v``
(0..1000, bit depth 10).

The topology is described inside a module-scoped fixture (never at
import: only one process may load libtpu, and every xdist worker
imports every test file), and everything compiles in this process with
the persistent compile cache off (an entry written for a described chip
cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from pilosa_tpu.config import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FIELD_TYPE_INT, FieldOptions
from pilosa_tpu.core.fragment import ROW_TILE
from pilosa_tpu.exec import keyplane
from pilosa_tpu.ops import pallas_kernels as pk
from pilosa_tpu.parallel import MeshPlanner, make_mesh
from pilosa_tpu.parallel import planner as planner_mod
from pilosa_tpu.parallel.mesh import shard_spec
from pilosa_tpu.pql import parse
from pilosa_tpu.sketch import kernels as sketch_kernels

N_SHARDS = 954
S_PAD = 1024
W = WORDS_PER_SHARD


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def index():
    """Schema only: a compile needs structure (fields, BSI depth,
    existence tracking), never data."""
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT, min=0, max=1000))
    return h, idx


class Plan:
    """The planner's own programs, traced from PQL by the planner's own
    signature walk, on a mesh of DESCRIBED devices handed to MeshPlanner
    (the program's default mesh is ``jax.devices()``, the CPU here)."""

    def __init__(self, holder, idx, devices):
        self.idx = idx
        self.mesh = make_mesh(devices)
        self.planner = MeshPlanner(holder, self.mesh)
        # Empty rows would sign as packed leaves; the 1B deployment's
        # rows are dense (density >= 0.01), so compile the dense class.
        self.planner.residency_packed_supported = False
        self.shards = tuple(range(N_SHARDS))
        assert self.planner._pad(N_SHARDS) == S_PAD
        self.row = jax.ShapeDtypeStruct((S_PAD, W), jnp.uint32,
                                        sharding=shard_spec(self.mesh))
        self.replicated = NamedSharding(self.mesh, PartitionSpec())

    def close(self):
        self.planner.close()

    def leaf_shapes(self, leaves):
        """ShapeDtypeStructs in the layout _fetch_leaf hands a program."""
        scalar = jax.ShapeDtypeStruct((), jnp.uint32,
                                      sharding=self.replicated)
        out = []
        for leaf in leaves:
            kind = leaf[0]
            if kind == "row":
                out.append(self.row)
            elif kind == "bsi":
                out.append((self.row, self.row, [self.row] * leaf[2]))
            elif kind == "bsiagg":
                cube = jax.ShapeDtypeStruct(
                    (leaf[2], S_PAD, W), jnp.uint32,
                    sharding=shard_spec(self.mesh, sharded_dim=1, ndim=3))
                out.append((self.row, self.row, cube))
            elif kind == "pred":
                out.append((scalar, scalar))
            else:
                raise AssertionError(f"unexpected leaf {leaf!r}")
        return out

    def count(self, pql):
        """(jitted fused count program, leaf shapes) of Count(<tree>)."""
        tree = parse(pql).calls[0].children[0]
        leaves = []
        sig = self.planner._signature(self.idx, tree, leaves, self.shards)
        fn = self.planner._compiled(("count",) + sig, sig, len(leaves),
                                    reduce="per_shard")
        return fn, self.leaf_shapes(leaves)

    def bitmap(self, pql):
        tree = parse(pql).calls[0]
        leaves = []
        sig = self.planner._signature(self.idx, tree, leaves, self.shards)
        fn = self.planner._compiled(("row",) + sig, sig, len(leaves),
                                    reduce=None)
        return fn, self.leaf_shapes(leaves)

    def topn_counts(self, pql, rows):
        """(the one-program filtered TopN pass, shapes): the filter
        tree's leaves, then ``rows`` candidate row stacks."""
        leaves = []
        filt_sig = self.planner._signature(self.idx, parse(pql).calls[0],
                                           leaves, self.shards)
        fn = self.planner._compiled_topn_counts(rows, filt_sig, len(leaves))
        return fn, self.leaf_shapes(leaves) + [self.row] * rows

    def sum(self, pql):
        call = parse(pql).calls[0]
        depth = self.idx.field("v").bsi_group.bit_depth
        leaves = [("bsiagg", "v", depth)]
        filt_sig = self.planner._signature(self.idx, call.children[0],
                                           leaves, self.shards)
        fn = self.planner._compiled_agg(("sum", False, depth, filt_sig),
                                        "sum", depth, filt_sig, False)
        return fn, self.leaf_shapes(leaves), depth


@pytest.fixture(scope="module")
def plan1(topo, index):
    p = Plan(*index, topo.devices[:1])
    yield p
    p.close()


@pytest.fixture(scope="module")
def plan4(topo, index):
    p = Plan(*index, topo.devices)
    yield p
    p.close()


def _stack(sharding, rows=S_PAD):
    return jax.ShapeDtypeStruct((rows, W), jnp.uint32, sharding=sharding)


# ---------------------------------------------------------------- kernels


@pytest.mark.parametrize("op", ["and", "or", "xor", "andnot"])
def test_pallas_pair_count_compiles(one_chip, op):
    x = _stack(one_chip)
    c = pk._pallas_pair_count.lower(x, x, op=op, interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows", [ROW_TILE, 8, 5])
def test_fragment_sweep_pair_count_compiles(one_chip, rows):
    """pair_count as core/fragment.intersection_counts_async calls it
    (the filtered-TopN row-count sweep): a row stack — a full ROW_TILE
    tile, or a small fragment's few dense rows — against ONE filter
    segment, broadcast inside the jit."""
    seg = jax.ShapeDtypeStruct((W,), jnp.uint32, sharding=one_chip)
    c = pk._pallas_pair_count.lower(_stack(one_chip, rows), seg, op="and",
                                    interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()


def test_pallas_kernel_refuses_an_operand_that_spans_the_mesh(topo):
    """Why MeshPlanner.execute_topn_counts puts the filter on one device
    before the per-fragment sweep: a slice of a planner stack spans the
    4-chip mesh, the jit over it is an SPMD program, and the chip's
    compiler does not partition a Pallas kernel. Interpret mode on four
    virtual CPU devices accepts it; the first four-chip run did not."""
    spans = NamedSharding(make_mesh(topo.devices), PartitionSpec())
    seg = jax.ShapeDtypeStruct((W,), jnp.uint32, sharding=spans)
    with pytest.raises(NotImplementedError, match="shard_map"):
        pk._pallas_pair_count.lower(_stack(spans, 8), seg, op="and",
                                    interpret=False).compile()


def test_keyplane_lookup_compiles(one_chip):
    plane = jax.ShapeDtypeStruct((3, 1 << 20), jnp.uint32, sharding=one_chip)
    probe = jax.ShapeDtypeStruct((4096,), jnp.uint32, sharding=one_chip)
    keyplane._lookup_jit.lower(plane, probe, probe).compile()


def test_hll_expand_compiles_at_a_quarter(one_chip):
    """The filtered-Distinct expand kernel at 256 shards. At the
    1024-shard bucket the chip's compiler REFUSES it (the program alone
    needs 16.0 GB of a v5e's 15.75 GB HBM; PERF.md, open questions), so
    this pins the largest shape checked to fit, not the headline one."""
    packed = jax.ShapeDtypeStruct((256, SHARD_WIDTH), jnp.int32,
                                  sharding=one_chip)
    expand = jax.jit(sketch_kernels.hll_expand, static_argnames=("p",))
    expand.lower(packed, _stack(one_chip, 256), p=12).compile()


# ------------------------------------------------------- planner programs


@pytest.mark.parametrize("pql", [
    "Count(Intersect(Row(f=1), Row(g=2)))",
    "Count(Xor(Intersect(Row(f=3), Row(g=4)), Not(Row(f=5))))",
    "Count(Row(v >= 250))",
])
def test_fused_count_compiles(plan1, pql):
    fn, shapes = plan1.count(pql)
    c = fn.lower(*shapes).compile()
    # One int32 per stack row leaves the device; the host sums them.
    assert c.memory_analysis().output_size_in_bytes == S_PAD * 4


def test_topn_filter_tree_compiles(plan1):
    """Filtered TopN evaluates its filter to a [S_pad, W] stack first
    (execute_topn_counts -> _tree_stack); the per-fragment sweep is
    test_fragment_sweep_pair_count_compiles."""
    fn, shapes = plan1.bitmap("Intersect(Row(g=2), Row(f=1))")
    c = fn.lower(*shapes).compile()
    assert c.memory_analysis().output_size_in_bytes == S_PAD * W * 4


@pytest.mark.parametrize("chips, pql", [
    (1, "Row(g=2)"),
    (1, "Intersect(Row(g=2), Not(Row(f=1)))"),
    (4, "Row(g=2)"),
])
def test_topn_counts_program_compiles(request, chips, pql):
    """The route a filtered TopN takes when the candidate rows' stacks
    fit (planner._topn_counts_stacked), at the rides index's shape: 8
    ``passenger_count`` rows over the 1,024-shard bucket. What comes
    back is the [8, 1024] int32 matrix; the rows are never stacked into
    a 1 GiB cube, and a filter that is more than a leaf is held once
    (one 128 MiB stack). On the mesh it is an SPMD program over the
    ``shard`` axis: a quarter of every operand a chip, nothing gathered."""
    plan = request.getfixturevalue(f"plan{chips}")
    fn, shapes = plan.topn_counts(pql, 8)
    c = fn.lower(*shapes).compile()
    text = c.as_text()
    assert "jit_topn_counts" in text
    assert "all-gather" not in text and "all-reduce" not in text
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == len(shapes) * S_PAD * W * 4 // chips
    assert ma.output_size_in_bytes == 8 * S_PAD * 4 // chips
    assert ma.temp_size_in_bytes <= (S_PAD * W * 4 + (8 << 20)) // chips


def test_bsi_sum_fold_compiles(plan1):
    fn, shapes, depth = plan1.sum("Sum(Row(v > 500), field=v)")
    assert depth == 10
    c = fn.lower(*shapes).compile()
    ma = c.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < 15 << 30, f"fused Sum needs {total} B of a 16 GB chip"


def test_group_by_steps_compile(plan1):
    row = plan1.row
    planner_mod._jit_and.lower(row, row).compile()
    planner_mod._jit_and_count.lower(row, row).compile()
    planner_mod._jit_count.lower(row).compile()


def test_sparse_upload_assemble_compiles(plan1):
    """_build_stack's COO scatter: a branch only the TPU backend takes
    (_sparse_upload_enabled), so no CPU test ever enters it."""
    rep = plan1.replicated

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    c = plan1.planner._assemble_jit.lower(
        arg((8,), jnp.int32), arg((8, W), jnp.uint32),
        arg((4096,), jnp.int32), arg((4096,), jnp.int32),
        arg((4096,), jnp.uint32), s_pad=S_PAD).compile()
    assert c.memory_analysis().output_size_in_bytes == S_PAD * W * 4


def test_sparse_upload_route_of_rows_over_the_coo_threshold(plan1,
                                                           monkeypatch):
    """The TPU-only branch with real rows at the real shard count. Rows
    over SPARSE_UPLOAD_MAX_BITS in every shard (what both benchmark
    cells hold): no COO, the stack is ONE [S_PAD, W] host matrix built
    in place and handed to device_put with the shard sharding. One
    shard under the threshold turns the same stack into the assemble
    program with 953 rows written into dmat[k], bucketed to 1,024: that
    one has to compile for the chip and fit beside a full budget."""
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(7)
    over = planner_mod.MeshPlanner.SPARSE_UPLOAD_MAX_BITS + 52
    cols = np.concatenate([
        rng.choice(SHARD_WIDTH, over, replace=False) + s * SHARD_WIDTH
        for s in range(N_SHARDS)])
    f.import_bits(np.ones(len(cols), dtype=np.uint64), cols)
    planner = MeshPlanner(h, plan1.mesh)
    monkeypatch.setattr(planner, "_sparse_upload_enabled", lambda: True)
    try:
        upload, nbytes = planner._build_stack(idx, "f", "standard", 1,
                                              plan1.shards)
        assert upload.func is jax.device_put and nbytes == S_PAD * W * 4
        mat, sharding = upload.args
        assert mat.shape == (S_PAD, W) and mat.dtype == np.uint32
        assert sharding == shard_spec(plan1.mesh)
        for i in (0, 500, N_SHARDS - 1):
            frag = h.fragment("i", "f", "standard", i)
            assert np.array_equal(mat[i], frag.row_words(1))
        assert not mat[N_SHARDS:].any()

        frag = h.fragment("i", "f", "standard", 17)
        frag.clear_row(1)
        f.set_bit(1, 17 * SHARD_WIDTH + 3)
        upload, _ = planner._build_stack(idx, "f", "standard", 1,
                                         plan1.shards)
        assert upload.func is planner._assemble_jit
        didx, dmat, ci, cw, cv = upload.args
        assert dmat.shape == (S_PAD, W) and 17 not in didx
        assert (ci[0], cw[0], cv[0]) == (17, 0, 8) and len(ci) == 8
        rep = plan1.replicated
        c = planner._assemble_jit.lower(
            *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
              for a in upload.args), **upload.keywords).compile()
        ma = c.memory_analysis()
        assert ma.output_size_in_bytes == S_PAD * W * 4
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes)
        assert total + (4 << 30) < 15 << 30, f"assemble needs {total} B"
    finally:
        planner.close()


def test_coalesced_vmap_wave_compiles(plan1):
    """Same-plan queries over DIFFERENT leaves launch as one vmapped
    [B, ...] program on a single-device mesh (parallel/coalesce)."""
    fn, shapes = plan1.count("Count(Intersect(Row(f=1), Row(g=2)))")
    pl = plan1.planner
    batched = [jax.ShapeDtypeStruct((2,) + s.shape, s.dtype,
                                    sharding=plan1.replicated)
               for s in shapes]
    pl.vmapped(pl.fn_key(fn), pl.fn_raw(fn)).lower(*batched).compile()


# ------------------------------------------------------------ four chips


def test_fused_count_on_four_chip_mesh(plan4):
    """The ('shard',) mesh over all four described chips, with the
    planner's NamedSharding on every leaf stack. The count program
    reduces per shard and the HOST sums (MeshPlanner._sum_host), so the
    compiled text holds no collective at all; what must hold is that no
    all-gather pulls the stacks onto one chip, that each chip is handed
    a quarter of the arguments, and that the counts come back sharded."""
    assert plan4.planner.n_devices == 4
    fn, shapes = plan4.count(
        "Count(Xor(Intersect(Row(f=3), Row(g=4)), Not(Row(f=5))))")
    assert len(shapes) == 4
    c = fn.lower(*shapes).compile()
    text = c.as_text()
    assert "all-gather" not in text and "all-reduce" not in text
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes == len(shapes) * S_PAD * W * 4 // 4
    assert ma.output_size_in_bytes == S_PAD * 4 // 4
