"""Compile the serving kernels and steps for a TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology: it rejects what interpret mode accepts (block shapes
off the (8, 128) tiling, kernels that overflow VMEM, programs larger than
the chip's 16 GB of HBM).  Shapes follow ``chip_smoke.py``.  The topology
is described inside a fixture, so importing this module touches no TPU
library; the persistent compilation cache is off around the compiles
(an entry written for a described chip cannot be read back here).
"""

from __future__ import annotations

import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.serving.engine import make_ranked_step, make_serve_step

HBM_BYTES = 16 * 10**9  # one v5e chip

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

MAIN_DOCS = chip_smoke.MAIN["np_docs"]
MAIN_POS_DOCS = chip_smoke.MAIN["pos_docs"]
BATCH = chip_smoke.MAIN["per_kind"]
KERNEL_DOCS = chip_smoke.KERNELS["docs"]
KERNEL_BATCH = chip_smoke.KERNELS["per_kind"]
N_LISTS = 2000  # the generator's vocabulary
#: array sizes per document of the repair_skip fused layout, measured on
#: 10,000 (non-positional) and 2,000 (positional) generated documents
NP_ANCHORS, NP_POOL = 5, 26
#: longest rule of the non-positional index at MAIN_DOCS, measured on the
#: chip (it equals the document count up to 10,000 docs)
NP_MAX_PHRASE = 16384
POS_ANCHORS, POS_POOL, POS_MAX_PHRASE = 38, 101, 99


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one v5e chip"
    return total


def _fused_index(one_chip, n_docs, anchors, pool, max_phrase, positional):
    s = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=one_chip)
    n_c = anchors * n_docs
    index = {"anchors": s((n_c,)), "c_offsets": s((N_LISTS + 1,)),
             "c_ptr": s((n_c,)), "c_len": s((n_c,)),
             "pool": s((pool * n_docs + max_phrase,)),
             "lengths": s((N_LISTS,))}
    if positional:
        index["doc_starts"] = s((n_docs,))
    return index


def _queries(one_chip, batch, width=2, fused=False):
    """Query terms, lengths and the window cursor: a scalar ``row_start``
    (dense), or each query's C-entry range ``row_lo, row_hi`` (fused)."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    cursor = (s((batch,)), s((batch,))) if fused else (s(()),)
    return s((batch, width)), s((batch,)), *cursor


#: temporaries of the cell's AND step (batch 16, max_phrase 16,384): the
#: window of 64 entries padded to max_phrase lanes needed 1.84 GB; the
#: compacted window of 16 x 16,384 lanes needs far less
AND_STEP_TEMP_BYTES = 200 * 10**6


@pytest.mark.parametrize("name,rows,lanes", [
    ("minhash_rows_2d", 2048, 256),       # mining: 200-word documents
    ("minhash_rows_2d", 2048, 2048),      # rlz: posting lists of 2,000 docs
    ("minhash_rows_2d", 1024, 51200),     # rows wider than one lane tile
    ("decode_rows_2d", KERNEL_BATCH * 64, 384),
    ("decode_rows_2d", BATCH * 64, 4096),
    ("decode_rows_2d", BATCH * 64, NP_MAX_PHRASE),  # the main path's
    ("decode_rows_2d", BATCH * 64, 50176),  # a max_phrase of 50,000
    ("probe_rows_2d", KERNEL_BATCH * 64 * 384, 384),
    ("probe_rows_2d", 1024, 50176),
    ("anchor_probe_sliced_2d", KERNEL_BATCH * 64 * 384, 12288),
])
def test_kernel_compiles_for_v5e(one_chip, name, rows, lanes):
    from repro.kernels.anchor_intersect.kernel import anchor_probe_sliced_2d
    from repro.kernels.fused_decode.kernel import decode_rows_2d, probe_rows_2d
    from repro.kernels.minhash_sig.kernel import minhash_rows_2d

    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    col = s((rows, 1))
    if name == "minhash_rows_2d":
        fn, args = minhash_rows_2d, (s((rows, lanes)), col, s((64,)), s((64,)))
    elif name == "decode_rows_2d":
        fn, args = decode_rows_2d, (s((rows, lanes)), col, col)
    elif name == "probe_rows_2d":
        fn, args = probe_rows_2d, (s((rows, lanes)), col, col, col)
    else:  # probes (rows, 1) against a (1, lanes) anchor row
        fn, args = anchor_probe_sliced_2d, (col, col, col, s((1, lanes)))
    compiled = _compile(lambda *a: fn(*a, interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("mode,doclist,topk", [
    ("and", False, 0), ("and", True, 0), ("and", False, 10),
    ("phrase", False, 0), ("phrase", True, 0)])
def test_fused_serve_step_fits_v5e(one_chip, mode, doclist, topk):
    """The main path's fused steps at its sizes: the window is
    (batch, window_lanes(max_phrase)) int32, max_phrase rounded up to a
    multiple of 1,024."""
    phrase = mode == "phrase"
    if phrase:
        index = _fused_index(one_chip, MAIN_POS_DOCS, POS_ANCHORS, POS_POOL,
                             POS_MAX_PHRASE, positional=True)
        max_phrase = POS_MAX_PHRASE
    else:
        index = _fused_index(one_chip, MAIN_DOCS, NP_ANCHORS, NP_POOL,
                             NP_MAX_PHRASE, positional=False)
        max_phrase = NP_MAX_PHRASE
    step = make_serve_step(max_terms=2, mode=mode, topk=topk, doclist=doclist,
                           layout="fused", max_phrase=max_phrase,
                           n_docs=float(MAIN_DOCS))
    compiled = _compile(step, index, *_queries(one_chip, BATCH, fused=True))
    _fits(compiled)
    if mode == "and" and not doclist and not topk and BATCH == 16:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < AND_STEP_TEMP_BYTES, temp


def test_named_and_step_compiles_for_v5e_at_the_cell_shape(one_chip):
    """The server's own AND step, under its program name and scopes, at
    the benchmark cell's window: a batch of 16 over a max_phrase of
    16,384, so 16 x 16,384 lanes, with the searches' step counts passed
    as the server passes them."""
    import numpy as np

    from repro.serving.engine import AND, BatchedServer

    server = BatchedServer(host_index=None, arrays={}, n_docs=float(MAIN_DOCS),
                           layout="fused", max_phrase=NP_MAX_PHRASE,
                           _lengths_np=np.zeros(0), _c_offsets_np=np.zeros(1),
                           _lane_cum_np=np.zeros(1))
    index = _fused_index(one_chip, MAIN_DOCS, NP_ANCHORS, NP_POOL,
                         NP_MAX_PHRASE, positional=False)
    steps = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    compiled = server._step(AND, 2).lower(
        index, *_queries(one_chip, 16, fused=True), steps).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_serve_and")
    assert "anchor_search" in text and "pool_search" in text
    _fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < AND_STEP_TEMP_BYTES


def test_kernel_serve_step_compiles_for_v5e(one_chip, monkeypatch):
    """probe="kernel" at the kernel phase's sizes holds compiled Pallas
    kernels (the step asks the backend whether to interpret; here the
    backend is the CPU, so the test answers for the chip)."""
    from repro.kernels import platform

    monkeypatch.setattr(platform, "interpret_mode", lambda: False)
    index = _fused_index(one_chip, KERNEL_DOCS, NP_ANCHORS, NP_POOL,
                         KERNEL_DOCS, positional=False)
    step = make_serve_step(max_terms=2, mode="and", layout="fused",
                           probe="kernel", max_phrase=KERNEL_DOCS)
    compiled = _compile(step, index,
                        *_queries(one_chip, KERNEL_BATCH, fused=True))
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_ranked_step_fits_v5e(one_chip):
    """BM25 over dense (n_lists, longest list) planes at the main size."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    index = {"rank_run_docs": s((N_LISTS, MAIN_DOCS), jnp.int32),
             "rank_run_tfs": s((N_LISTS, MAIN_DOCS), jnp.float32),
             "rank_run_valid": s((N_LISTS, MAIN_DOCS), jnp.bool_),
             "rank_doc_norm": s((MAIN_DOCS,), jnp.float32),
             "rank_idf": s((N_LISTS,), jnp.float32)}
    step = make_ranked_step(max_terms=2, topk=10)
    _fits(_compile(step, index, *_queries(one_chip, BATCH)))


@pytest.fixture(scope="module")
def mesh(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), ("data",))


@pytest.mark.parametrize("mode", ["and", "phrase"])
def test_partitioned_step_compiles_for_v5e_mesh(mesh, mode):
    """The four-chip phase's shard_map step over a 2x2 mesh: every probe
    is shard-local, so the compiled program holds no collective."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.serving.partitioned import make_partitioned_serve_step

    def s(shape, dt=jnp.int32, spec=None):
        spec = P("data", *([None] * (len(shape) - 1))) if spec is None else spec
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, spec))

    four = chip_smoke.FOUR_CHIPS
    n_c = max(NP_ANCHORS * four["np_docs"],  # C entries per shard
              POS_ANCHORS * four["pos_docs"]) // 4
    arrays = {"anchors": s((4, n_c)), "c_offsets": s((4, N_LISTS + 1)),
              "expand": s((4, n_c, 32)),
              "expand_valid": s((4, n_c, 32), jnp.bool_),
              "lengths": s((4, N_LISTS)), "doc_base": s((4,))}
    per_kind = four["per_kind"]
    queries = (s((per_kind, 2), spec=P()), s((per_kind,), spec=P()),
               s((), spec=P()), s((2,), spec=P()))  # cursor, search bounds
    step = make_partitioned_serve_step(max_terms=2, mesh=mesh, mode=mode)
    compiled = _compile(step, arrays, *queries)
    text = compiled.as_text()
    assert not any(op in text for op in ("all-gather", "all-reduce",
                                         "all-to-all", "collective-permute"))
    _fits(compiled)
