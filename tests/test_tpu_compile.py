"""The round path's kernels and the pod gossip program, compiled for a
described TPU v5e (no chip needed): what interpret mode cannot show, such as
block shapes and memory spaces the TPU compiler refuses. Compiling is not
running, so these tests say nothing about results or speed."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fnn_spec():
    """FlatSpec of fnn_mnist's 3FNN (784-200-200-10)."""
    from repro.core.flatten import make_flat_spec
    from repro.models import make_fnn

    model = make_fnn((200, 200))
    return make_flat_spec(jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("with_base", [False, True])
def test_payload_qdq_compiles_to_kernel(one_chip, fnn_spec, bits, with_base):
    """The protocol round's fused qdq on an n=100 payload of the 3FNN
    (155,900 rows of 128 lanes), per-message, with and without the fused
    receiver base."""
    from repro.kernels.quantize import payload_quantize_dequantize

    rows = 100 * fnn_spec.rows
    assert rows == 155_900
    payload = jax.ShapeDtypeStruct((100, fnn_spec.d_pad), jnp.float32,
                                   sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def qdq(p, k, base=None):
        return payload_quantize_dequantize(p, fnn_spec, per_message=True,
                                           bits=bits, key=k, base=base,
                                           interpret=False)

    args = (payload, key) + ((payload,) if with_base else ())
    assert "tpu_custom_call" in _hlo(qdq, *args)


def test_hop_qdq_compiles_to_kernel(one_chip, fnn_spec):
    """The walk's Eq. 13 hop hand-off: one wire tensor per leaf across the
    M=10 chain rows (``per_message=False``), with the receiver's base
    fused."""
    from repro.kernels.quantize import payload_quantize_dequantize

    chains = jax.ShapeDtypeStruct((10, fnn_spec.d_pad), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def hop(diff, k, base):
        return payload_quantize_dequantize(diff, fnn_spec, per_message=False, bits=8,
                                           key=k, base=base, interpret=False)

    assert "tpu_custom_call" in _hlo(hop, chains, key, chains)


_PERMUTE_RE = re.compile(
    r"= \(?(\w+)\[([\d,]*)\][^ ]* .*?collective-permute(?:-start)?\(")
_ITEMSIZE = {"s8": 1, "bf16": 2, "f32": 4}


def _permutes(text):
    """(dtype, element count) of every collective-permute's operand."""
    return [(dt, int(np.prod([int(d) for d in dims.split(",") if d])))
            for dt, dims in _PERMUTE_RE.findall(text)]


@pytest.mark.parametrize("bits", [32, 8])
def test_ring_gossip_on_four_chips_is_collective_permute_only(topo, bits):
    """DFedRW's claim at pod scale: the gossip mix moves params between
    chips by collective-permute and never all-reduces. At 8 bits the leaf
    permutes carry the int8 codes and together move `wire_bytes`; at 32
    bits they carry the f32 leaf."""
    from repro.dist.gossip import GossipConfig, gossip_mix, wire_bytes
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("pod",), topo.devices)
    spec = P("pod", None, None)
    tree = {"w": jax.ShapeDtypeStruct((4, 1024, 128), jnp.float32,
                                      sharding=NamedSharding(mesh, spec)),
            "b": jax.ShapeDtypeStruct((4, 256), jnp.float32,
                                      sharding=NamedSharding(mesh, P("pod", None)))}
    specs = {"w": spec, "b": P("pod", None)}
    cfg = GossipConfig(axis="pod", topology="ring", quant_bits=bits)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    text = _hlo(lambda t, k: gossip_mix(t, specs, mesh, cfg, k), tree, key)
    assert "collective-permute" in text
    assert "all-reduce" not in text
    permutes = _permutes(text)
    leaf_sizes = {1024 * 128, 256}
    leaf_types = {dt for dt, size in permutes if size in leaf_sizes}
    assert leaf_types == ({"s8"} if bits == 8 else {"f32"}), permutes
    assert sum(size * _ITEMSIZE[dt] for dt, size in permutes) == wire_bytes(tree, cfg, 4)


def _computations(text):
    """HLO computation name -> its text."""
    comps, name, lines = {}, None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head and not line.startswith(" "):
            name, lines = head.group(1), []
        elif line.startswith("}") and name is not None:
            comps[name] = "\n".join(lines)
            name = None
        elif name is not None:
            lines.append(line)
    return comps


def _reached(comps, roots):
    """The computations ``roots`` run, with those they call, transitively."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", comps[c])
    return seen


@pytest.mark.parametrize("bits", [8, 32])
def test_round_program_merges_rows_in_place(one_chip, monkeypatch, bits):
    """The flat round program at a small shape (n=20, d_pad 203,648, where
    XLA turns a row scatter into a loop of row updates): no loop writes rows
    of the donated (n, d_pad) matrix one at a time, no scatter writes it,
    and the row merge's kernel writes it in place (aliased to the matrix
    operand): once at bits < 32, twice (winners, then the Eq. 11 rows) at
    32."""
    from repro.core import DFedRW, DFedRWConfig, QuantConfig, make_topology
    from repro.core.heterogeneity import partition_similarity
    from repro.data import FederatedDataset, synthetic_image_classification
    from repro.models import make_fnn

    # the kernels take their compiled branch, as on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, m, k, b = 20, 4, 3, 8
    x, y = synthetic_image_classification(n_samples=200, seed=0, noise=1.0)
    data = FederatedDataset.from_partition(
        x, y, partition_similarity(y, n, 50, np.random.default_rng(0)))
    runner = DFedRW(make_fnn((256,)), data, make_topology("complete", n),
                    DFedRWConfig(m_chains=m, k_walk=k, batch_size=b,
                                 quant=QuantConfig(bits=bits)))
    d = runner.flat_spec.d_pad
    a, n_agg = 5, 5

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg((n, d), jnp.float32), arg((m, k), jnp.int32), arg((m, k), jnp.bool_),
            arg((m, k, b), jnp.int32), arg((a, n_agg), jnp.int32),
            arg((a, n_agg), jnp.float32), arg((a,), jnp.int32), arg((), jnp.int32),
            arg((2,), jnp.uint32))
    text = runner.round_program(bits).lower(*args).compile().as_text()
    comps = _computations(text)
    bodies = re.findall(r"body=%([\w.\-]+)", text)
    matrix = f"f32[{n},{d}]"
    for c in _reached(comps, bodies):
        for line in comps[c].splitlines():
            assert not (matrix in line and "dynamic-update-slice(" in line), line
    assert not [line for line in text.splitlines()
                if re.search(rf"= {re.escape(matrix)}\S* scatter\(", line)]
    merges = [line for line in text.splitlines()
              if re.search(rf"%rowmerge[\w.]* = f32\[{n},{d}\]", line)]
    assert len(merges) == (1 if bits < 32 else 2), merges
    for line in merges:
        assert 'custom_call_target="tpu_custom_call"' in line
        assert "output_to_operand_aliasing={{}: (1, {})}" in line, line


@pytest.mark.parametrize("n,k,m,kernel", [(13, 2, 5, True), (100_000, 8, 10_000, True),
                                          (1_000, 8, 13_000, False)])
def test_merge_rows_kernel_or_scatter_by_size(one_chip, monkeypatch, n, k, m, kernel):
    """``merge_rows`` compiles to the in-place kernel while a column block
    of its set and add rows fits in VMEM (n=13: a partial last row block;
    the fleet shapes' K*M = 80,000 set rows), and to XLA's row scatter
    beyond that (104,000)."""
    from repro.kernels.rowmerge import merge_rows

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, a = 2 * 128, 3

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(merge_rows, donate_argnums=0).lower(
        arg((n, d), jnp.float32), arg((k, m), jnp.int32), arg((k, m, d), jnp.float32),
        arg((a,), jnp.int32), arg((a, d), jnp.float32)).compile().as_text()
    assert ('custom_call_target="tpu_custom_call"' in text) == kernel
    if kernel:
        assert "output_to_operand_aliasing={{}: (1, {})}" in text
    else:
        assert re.search(rf"= f32\[{n},{d}\]\S* scatter\(", text)
