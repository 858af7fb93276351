"""Compile rehearsals: the main path's programs, built by the TPU's own
compiler for a v5e that is described and not attached.

Nothing runs here — a compile says nothing of results or time — but what
the chip's compiler refuses (a misaligned kernel block, a donated buffer
it cannot reuse, a program that does not fit) fails in tier-1 instead of
on the chip. The deployment sizes themselves compile on the chip, in the
benchmark's set-up (``benchmarks/run.py``): too slow for tier-1.

The topology is described inside a fixture and never at import: one
process at a time may load the TPU library, so only the worker that runs
this file does. The persistent compile cache is off around the compiles
(an entry written for a described chip cannot be read back without one).
"""

from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from copycat_tpu.models.raft_groups import _jitted_programs  # noqa: E402
from copycat_tpu.ops.apply import ResourceConfig  # noqa: E402
from copycat_tpu.ops.consensus import (  # noqa: E402
    Config,
    Submits,
    deep_scan,
    deep_step,
    init_state,
    query_step,
    step,
)
from copycat_tpu.ops.pallas_kernels import (  # noqa: E402
    kth_largest_pallas,
    ring_window_pallas,
)

@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices), ("groups",))


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


# -- shapes: what each program is called with, placed by ``place`` --------

def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def group_placer(sharding):
    """``place(ndim)`` for one chip (everything on it) or for a
    ``('groups',)`` mesh (leading axis sharded, the rest replicated)."""
    if isinstance(sharding, Mesh):
        return lambda ndim: NamedSharding(
            sharding, P("groups", *([None] * (ndim - 1))))
    return lambda ndim: sharding


def replicated(sharding):
    if isinstance(sharding, Mesh):
        return NamedSharding(sharding, P())
    return sharding


def state_shapes(G, P_, L, config, sharding):
    place = group_placer(sharding)
    shapes = jax.eval_shape(
        partial(init_state, G, P_, L, config=config),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree.map(
        lambda x: _struct(x.shape, x.dtype, place(x.ndim)), shapes)


def submit_shapes(lead, sharding, tag_width=None):
    """``Submits`` with leading dims ``lead`` (``(G, S)`` or ``(W, G, S)``);
    the group axis is the one before last."""
    if isinstance(sharding, Mesh):
        spec = [None] * len(lead)
        spec[-2] = "groups"
        sh = NamedSharding(sharding, P(*spec))
    else:
        sh = sharding
    i32 = _struct(lead, jnp.int32, sh)
    tag = i32 if tag_width is None else _struct(
        (*lead[:-1], tag_width), jnp.int32, sh)
    return Submits(opcode=i32, a=i32, b=i32, c=i32, tag=tag,
                   valid=_struct(lead, jnp.bool_, sh))


def step_args(G, P_, L, S, config, sharding):
    place = group_placer(sharding)
    return (state_shapes(G, P_, L, config, sharding),
            submit_shapes((G, S), sharding),
            _struct((G, P_, P_), jnp.bool_, place(3)),
            _struct((2,), jnp.uint32, replicated(sharding)))


def compile_step(G, P_, L, S, config, sharding):
    return jax.jit(partial(step, config=config)).lower(
        *step_args(G, P_, L, S, config, sharding)).compile()


def round_args(G, P_, L, S, config, sharding, planes=6):
    """Arguments of the served round's programs as ``RaftGroups`` calls
    them (``models/raft_groups.py:_jitted_programs``, the fused rounds
    too): the submits' six planes side by side in one buffer; a query's
    seven (``planes=7``) follow the state alone."""
    state, _, deliver, key = step_args(G, P_, L, S, config, sharding)
    packed = _struct((G, planes * S), jnp.int32, group_placer(sharding)(2))
    return (state, packed, deliver, key) if planes == 6 else (state, packed)


def joint_args(G, P_, L, S, config, sharding, slots=1):
    """Arguments of the round that takes a read window's rows along: the
    query's seven planes, ``slots`` wide each, behind the submits' six in
    the one buffer, and ``slots`` (static)."""
    state, _, deliver, key = step_args(G, P_, L, S, config, sharding)
    packed = _struct((G, 6 * S + 7 * slots), jnp.int32,
                     group_placer(sharding)(2))
    return state, packed, deliver, key, slots


def deep_args(G, P_, L, S, B, config, sharding, windows=None):
    """Arguments of ``deep_step`` (``windows=None``) or ``deep_scan``."""
    place = group_placer(sharding)
    state, _, deliver, key = step_args(G, P_, L, S, config, sharding)
    acc = lambda dt: _struct((G, B), dt, place(2))
    head = (state, acc(jnp.int32), acc(jnp.bool_), acc(jnp.int32),
            _struct((G,), jnp.bool_, place(1)),
            _struct((G,), jnp.int32, place(1)))
    if windows is None:
        return (*head, _struct((), jnp.int32, replicated(sharding)),
                submit_shapes((G, S), sharding, tag_width=1), deliver, key)
    return (*head, submit_shapes((windows, G, S), sharding, tag_width=1),
            deliver, key)


def compile_deep(G, P_, L, S, B, config, sharding, windows=None):
    """The deep drive's program exactly as ``models/bulk.py`` jits it on
    the chip: state and accumulators donated."""
    fn = deep_step if windows is None else deep_scan
    onehot = isinstance(sharding, Mesh)
    return jax.jit(partial(fn, config=config, onehot=onehot),
                   donate_argnums=(0, 1, 2, 3, 4)).lower(
        *deep_args(G, P_, L, S, B, config, sharding, windows)).compile()


def compile_query(G, P_, L, S, config, sharding):
    place = group_placer(sharding)
    return jax.jit(partial(query_step, config=config)).lower(
        state_shapes(G, P_, L, config, sharding),
        submit_shapes((G, S), sharding),
        _struct((G, S), jnp.bool_, place(2))).compile()


def collectives_in(compiled) -> dict:
    from copycat_tpu.parallel.scaling import census_text

    return census_text(compiled.as_text())


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# -- the cases ------------------------------------------------------------

@pytest.mark.parametrize("P_", [3, 5, 7])
def test_tally_kernel_compiles_for_the_chip(one_chip, P_):
    x = _struct((10_000, P_), jnp.int32, one_chip)
    compiled = kth_largest_pallas.lower(
        x, k=P_ // 2 + 1, interpret=False).compile()
    assert has_kernel(compiled)


@pytest.mark.parametrize("G,L,A", [
    (100_000, 32, 16),      # the raw and bulk cells' ring and window
    (10_000, 64, 16),
    (1_000, 32, 32),
    (1_000, 24, 16),        # a ring that is no power of two
])
def test_ring_window_kernel_compiles_for_the_chip(one_chip, G, L, A):
    compiled = ring_window_pallas.lower(
        _struct((G, 5, L), jnp.int32, one_chip),
        _struct((G, 5), jnp.int32, one_chip), A=A).compile()
    assert has_kernel(compiled)
    # the planes go in and the windows come out as they lie: a copy here
    # is a pass over a log plane that the one-hot form did not make
    entry = compiled.as_text().split("ENTRY", 1)[1]
    assert " copy(" not in entry and " transpose(" not in entry, entry


@pytest.mark.parametrize("G,P_,L,S,resource", [
    (1024, 3, 64, 4, ResourceConfig()),                 # engine default
    (10_000, 3, 64, 16, ResourceConfig.counters_only()),  # bench counter
], ids=["engine-default", "counter-10kx3"])
def test_step_compiles_with_the_kernel_inside(one_chip, G, P_, L, S,
                                              resource):
    config = Config(use_pallas=True, resource=resource,
                    append_window=max(4, S), applies_per_round=max(4, S))
    compiled = compile_step(G, P_, L, S, config, one_chip)
    # Config alone decides: the program holds the Mosaic kernel although
    # this process's default backend is the CPU
    assert has_kernel(compiled)


def test_deep_scan_compiles_with_donation(one_chip):
    config = Config(monotone_tag_accept=True,
                    resource=ResourceConfig.counters_only(),
                    append_window=16, applies_per_round=16)
    shape = (10_000, 3, 64, 16, 64, config, one_chip)
    compiled = compile_deep(*shape, windows=64 // 16 + 3)
    # the donated state + accumulators are reused in place (a refused
    # donation would hold the whole state twice on the chip)
    donated = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        deep_args(*shape, windows=64 // 16 + 3)[:5]))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 0.95 * donated, (mem, donated)


def test_query_step_compiles(one_chip):
    compile_query(1024, 3, 64, 8, Config(), one_chip)


def aliased_outputs(compiled) -> int:
    """Outputs the compiled module writes over a donated input."""
    header = compiled.as_text().split("\n", 1)[0]
    return header.count("may-alias") + header.count("must-alias")


def test_served_round_aliases_the_state_and_packs_the_rest(one_chip):
    """The served cells' step (DeviceEngineConfig defaults: G 1,024, P 3,
    L 64, S 4): every state leaf and the key are written in place, and
    what is left for the runtime to allocate and the host to fetch is one
    slab per dtype."""
    config = Config()
    args = round_args(1024, 3, 64, 4, config, one_chip)
    step_program, query_program, _, joint_program = _jitted_programs(config)
    compiled = step_program.lower(*args).compile()
    donated = len(jax.tree.leaves(args[0])) + 1
    outputs = len(jax.tree.leaves(compiled.output_shardings))
    assert aliased_outputs(compiled) == donated
    assert outputs - donated == 2
    donated_bytes = sum(x.size * x.dtype.itemsize
                        for x in jax.tree.leaves((args[0], args[3])))
    assert compiled.memory_analysis().alias_size_in_bytes >= donated_bytes
    # the query reads the state again: nothing donated, one slab out
    query = query_program.lower(
        *round_args(1024, 3, 64, 4, config, one_chip, planes=7)).compile()
    assert aliased_outputs(query) == 0
    assert len(jax.tree.leaves(query.output_shardings)) == 1
    # both in one call: the state and the key in place as in the round,
    # the round's two slabs and the query's one to allocate and fetch,
    # under a name the benchmark's map plane counts among the rounds'
    joint = joint_program.lower(
        *joint_args(1024, 3, 64, 4, config, one_chip)).compile()
    assert aliased_outputs(joint) == donated
    assert len(jax.tree.leaves(joint.output_shardings)) - donated == 3
    assert joint.as_text().split("\n", 1)[0].split()[1].startswith(
        "jit_round_")


def test_group_sharded_served_round_has_zero_collectives(four_chips):
    """Packed group-leading, the round's slabs stay shard-local: the
    engine's program over a ('groups',) mesh still talks to no other
    chip, and still writes its state in place."""
    config = Config()
    args = round_args(4096, 3, 64, 4, config, four_chips)
    compiled = _jitted_programs(config)[0].lower(*args).compile()
    assert collectives_in(compiled) == {}
    assert aliased_outputs(compiled) == len(jax.tree.leaves(args[0])) + 1
    # and so does the round that takes a read window along: a mesh engine
    # gets it for nothing
    joint = _jitted_programs(config)[3].lower(
        *joint_args(4096, 3, 64, 4, config, four_chips)).compile()
    assert collectives_in(joint) == {}
    assert aliased_outputs(joint) == len(jax.tree.leaves(args[0])) + 1


@pytest.mark.parametrize("chips", [1, 4])
def test_snapshot_cut_is_fresh_slabs_and_shard_local(one_chip, four_chips,
                                                     chips):
    """The snapshot capture's cut (``models/checkpoint._cut_program``) at
    the served cells' size: three buffers out for 56 in (one slab per
    dtype and the key), none of them an input's, and over a ('groups',)
    mesh no chip asks another for anything."""
    from copycat_tpu.models.checkpoint import _cut_program

    sharding = one_chip if chips == 1 else four_chips
    state, _, deliver, key = round_args(1024 * chips, 3, 64, 4, Config(),
                                        sharding)
    compiled = _cut_program.lower(state, deliver, key).compile()
    slabs, key_out = compiled.output_shardings
    assert sorted(slabs) == ["bool", "int32"] and key_out is not None
    assert aliased_outputs(compiled) == 0
    assert collectives_in(compiled) == {}
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves((state, deliver)))
    # (the analysis counts one chip's share)
    assert compiled.memory_analysis().output_size_in_bytes \
        >= state_bytes // chips


@pytest.mark.parametrize("pallas,L,S", [
    (False, 64, 4), (True, 64, 4),
    # the bulk cell's ring and window: the committed window's kernel too
    (True, 32, 16),
], ids=["jnp", "pallas", "pallas-window16"])
def test_group_sharded_step_has_zero_collectives(four_chips, pallas, L, S):
    # RaftGroups(mesh=...) hands the kernel its mesh the same way: left
    # alone, the TPU compiler refuses a Mosaic kernel on sharded operands
    config = Config(use_pallas=pallas, append_window=S,
                    applies_per_round=S,
                    kernel_mesh=four_chips if pallas else None)
    compiled = compile_step(4096, 3, L, S, config, four_chips)
    assert collectives_in(compiled) == {}
    assert has_kernel(compiled) == pallas
    assert ("ring_window_pallas" in compiled.as_text()) == (S == 16)


def test_a_bucketed_map_round_writes_its_table_in_place(one_chip):
    """``map-1kx10k``'s engine (1,024 x 3, a map table of 16,384 slots =
    64 buckets and no other pool): the round stores buckets into the
    donated table, 805 MB, and holds no second copy of it (a relayout
    between the scatter and the loop around ``clear`` once cost one a
    turn); a query evaluation holds one replica's view only in the branch
    that a ``contains_value`` takes."""
    pools = dict.fromkeys(ResourceConfig._fields, 0) | {"map_slots": 16384}
    config = Config(resource=ResourceConfig(**pools))
    args = round_args(1024, 3, 64, 4, config, one_chip)
    table = args[0].resources.map_table
    assert table.shape == (1024, 3, 64, 8, 128)
    table_bytes = table.size * table.dtype.itemsize
    step_program, query_program, _, joint_program = _jitted_programs(config)
    mem = step_program.lower(*args).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= table_bytes
    assert mem.temp_size_in_bytes < table_bytes // 8, mem
    query = query_program.lower(
        *round_args(1024, 3, 64, 1, config, one_chip, planes=7)).compile()
    assert query.memory_analysis().temp_size_in_bytes <= table_bytes // 2
    # the round with a read window's gets behind its puts: the table still
    # in place, and no more beside it than the query holds alone
    mem = joint_program.lower(
        *joint_args(1024, 3, 64, 4, config, one_chip)).compile(
        ).memory_analysis()
    assert mem.alias_size_in_bytes >= table_bytes
    assert mem.temp_size_in_bytes <= table_bytes // 2, mem
