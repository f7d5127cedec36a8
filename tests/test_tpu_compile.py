"""Compile the engine-path Pallas kernels for a described TPU v5e.

Interpret mode does not enforce Mosaic's tiling rules, so these tests
lower and compile each kernel -- and one whole served engine step -- for
a v5e chip that is described, not attached (the TPU compiler ships with
libtpu). Nothing runs: a pass says the chip's compiler accepts the
program at real widths, not that its answers are right (the interpret
sweeps in ``test_kernels.py`` and ``chip_smoke.py`` on the chip say that).

The topology is described inside a module fixture, never at import: only
one process may hold libtpu, and every xdist worker imports every test
file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import search_batch as sb
from repro.core.graph import HnswGraph
from repro.core.quantize import QuantizedStore
from repro.core.search import SearchParams
from repro.kernels import ops
from repro.kernels.bit_test import second_hop_eligible_pallas
from repro.kernels.distance_matrix import distance_matrix_pallas
from repro.kernels.gather_distance import (
    gather_distance_batch_pallas, gather_distance_pallas,
    quantized_gather_distance_batch_pallas, quantized_gather_distance_pallas)
from repro.kernels.quantized import quantized_distance_pallas

N = 1_000_000            # the deployment the chip smoke serves
WIDTHS = (128, 768, 1024)  # 1024 = GIST's 960 lane-padded at residency
B, K = 64, 96


@pytest.fixture(scope="module")
def one_chip():
    # the TPU compiler logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _store_copies(text: str, shape: str) -> list[str]:
    """Ops (other than the parameter itself and tuple plumbing) whose
    result has the store's shape: a relayout, pad or copy of the whole
    store."""
    pat = re.compile(r"^\s*%\S+ = " + re.escape(shape) + r"\{[^}]*\} (\S+)\(",
                     re.M)
    return [op for op in pat.findall(text)
            if op not in ("parameter", "get-tuple-element")]


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("kind", ["batch", "single"])
@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_gather_kernels_compile(one_chip, d, kind, dtype):
    quant = dtype == "int8"
    store = _sds((N, d), jnp.int8 if quant else jnp.float32, one_chip)
    scale = _sds((N,), jnp.float32, one_chip)
    if kind == "batch":
        q = _sds((B, d), jnp.float32, one_chip)
        ids = _sds((B, K), jnp.int32, one_chip)
        fn = (quantized_gather_distance_batch_pallas if quant
              else gather_distance_batch_pallas)
    else:
        q = _sds((d,), jnp.float32, one_chip)
        ids = _sds((K,), jnp.int32, one_chip)
        fn = (quantized_gather_distance_pallas if quant
              else gather_distance_pallas)
    args = (q, store, scale, ids) if quant else (q, store, ids)
    compiled = fn.lower(*args, metric="cos").compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the store is read in place: no relayout copy of it per call
    assert not _store_copies(text, f"{'s8' if quant else 'f32'}[{N},{d}]")


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("quant", [False, True])
def test_distance_matrix_kernels_compile(one_chip, d, quant):
    n = 65_536
    q = _sds((128, d), jnp.float32, one_chip)
    if quant:
        codes = _sds((n, d), jnp.int8, one_chip)
        scale = _sds((n,), jnp.float32, one_chip)
        compiled = quantized_distance_pallas.lower(
            q, codes, scale, "l2", bq=128).compile()
    else:
        x = _sds((n, d), jnp.float32, one_chip)
        compiled = distance_matrix_pallas.lower(q, x, "l2", bq=128).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _engine_step_text(one_chip, n, quant, d=768):
    """The optimized HLO of the served chunk program (PAPER_INDEX degrees,
    k=100, efs=200, 64 lanes, 32 steps) at n rows, compiled for the chip
    with the Pallas paths on."""
    m_l, m_u, n_up = 64, 32, n // 20
    params = SearchParams(k=100, efs=200, heuristic=3, metric="cos")
    if quant:
        vectors = QuantizedStore(codes=_sds((n, d), jnp.int8, one_chip),
                                 scale=_sds((n,), jnp.float32, one_chip))
    else:
        vectors = _sds((n, d), jnp.float32, one_chip)
    graph = HnswGraph(
        lower=_sds((n, m_l), jnp.int32, one_chip),
        lower_deg=_sds((n,), jnp.int32, one_chip),
        upper=_sds((n_up, m_u), jnp.int32, one_chip),
        upper_deg=_sds((n_up,), jnp.int32, one_chip),
        upper_ids=_sds((n_up,), jnp.int32, one_chip),
        entry_pos=_sds((), jnp.int32, one_chip),
        vectors=vectors)
    state = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: sb.parked_state(n, B, params)))
    words = -(-n // 32)
    return sb.engine_steps_overlap.lower(
        graph, _sds((B, d), jnp.float32, one_chip),
        _sds((B, words), jnp.uint32, one_chip), state, params, 32,
        sigma_g=_sds((B,), jnp.float32, one_chip),
        efs_lanes=_sds((B,), jnp.int32, one_chip)).compile().as_text()


@pytest.mark.parametrize("quant", [False, True])
def test_engine_steps_compiles_with_kernel(one_chip, monkeypatch, quant):
    """The served engine step at 1M x 768 compiles for the chip with the
    gather kernel in its loop, and never copies the vector store (a
    per-iteration relayout or pad of it would show as a store-shaped
    op)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.delenv(sb.GATHER_ENV, raising=False)
    text = _engine_step_text(one_chip, N, quant)
    assert "tpu_custom_call" in text
    assert not _store_copies(text, f"{'s8' if quant else 'f32'}[{N},768]")


@pytest.mark.parametrize("n", [65_536, N])
def test_second_hop_eligible_kernel_compiles(one_chip, n):
    """The second hop's eligibility kernel at the cells' n and at 1M (W
    31,250: padded to 245 rows of 128 words), 64 lanes x 64² ids."""
    words = -(-n // 32)
    tables = _sds((B, words), jnp.uint32, one_chip)
    compiled = second_hop_eligible_pallas.lower(
        tables, tables, _sds((B, 64 * 64), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _word_gathers(text: str, result: str, table: str) -> list[str]:
    """Gathers of shape ``result`` whose table (first operand) has shape
    ``table``: optimized HLO names operands without their types, so the
    table's shape comes from its defining instruction."""
    types = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\w+\[[\d,]*\])", text,
                            re.M))
    pat = re.compile(r"^\s*(?:ROOT )?%\S+ = " + re.escape(result)
                     + r"\{[^}]*\} gather\((%[^,]+),", re.M)
    return [op for op in pat.findall(text) if types.get(op) == table]


def test_engine_step_tests_the_second_hop_in_one_kernel(one_chip,
                                                        monkeypatch):
    """At the cells' n (65,536: a lane's bitset is u32[2048]) the served
    chunk program no longer gathers single words of S and of the visited
    set for the second hop's 64 x 64 ids per lane: the eligibility kernel
    does, under the ``navix.semimask`` scope."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.delenv(sb.GATHER_ENV, raising=False)
    n, shapes = 65_536, ("u32[64,4096]", "u32[64,2048]")
    text = _engine_step_text(one_chip, n, quant=False)
    assert not _word_gathers(text, *shapes)
    kernel = re.findall(r"^\s*%second_hop_eligible_pallas\S* = .*"
                        r'op_name="([^"]*)"', text, re.M)
    assert len(kernel) == 1 and "/navix.semimask/" in kernel[0]
    # with the kernel's row limit at 0 the two XLA word gathers return
    monkeypatch.setattr(ops, "BIT_TEST_MAX_ROWS", 0)
    jax.clear_caches()
    try:
        text = _engine_step_text(one_chip, n, quant=False)
    finally:
        jax.clear_caches()
    assert len(_word_gathers(text, *shapes)) == 2
