"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on the CPU and cost no chip time.
They compile at the widths of the 100^3 grid's segments at capacity 64 (384
local vertex, 1408 edge, 1920 face and 896 tet slots) and a 64-segment
launch:

  - the xla ``relation_block`` for every offloaded relation, the xla
    completion gather, and the drivers' whole-mesh jits over completed TT
    (HBM checked) — what the engine and drivers run on the chip;
  - each Pallas kernel the compiler accepts;
  - for each Pallas kernel it refuses, that ``backend="pallas"`` raises
    ``KernelCompileError`` naming the relation.

The topology is described only inside the module-scoped fixture below, never
at import: one process at a time may load the TPU library.

The file also holds the CPU checks of ``chip_smoke.py``'s check functions
and of ``backend="pallas"`` refusing to run without a TPU.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.engine import RelationEngine
from repro.core.mesh import segment_mesh
from repro.core.segtables import OFFLOADED_RELATIONS, RELATION_TABLES
from repro.core.segtables import precondition
from repro.data.meshgen import structured_grid
from repro.errors import KernelCompileError
from repro.kernels import completion_gather as cg
from repro.kernels import ops
from repro.kernels import segment_relations as sr

B = 64                       # segments per launch (engine batch_max)
NV = 384                     # local vertex slots per segment
WIDTH = {"V": (NV, 1), "E": (1408, 2), "F": (1920, 3), "T": (896, 4)}
# sparse entry kernels the TPU compiler refuses: every one gathers across
# lanes in-kernel, and Mosaic gathers only within one (8, 128) vreg
PALLAS_REFUSED = ("VV", "VE", "VF", "VT", "EF", "ET", "FT", "TT")
PALLAS_COMPILED = ("EE", "FF")   # one-hot counts kernel + jitted epilogue


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # no skip: a missing TPU compiler or a held TPU library must fail here
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _tables(relation, sharding):
    kx, ky = RELATION_TABLES[relation]
    nx, ax = WIDTH[kx]
    ny, ay = WIDTH[ky]
    return (_sds((B, nx, ax), sharding), _sds((B, ny, ay), sharding),
            _sds((B, ny), sharding))


def _compile_block(relation, backend, sharding):
    tx, ty, colg = _tables(relation, sharding)
    fn = functools.partial(ops.relation_block, relation, nvl=NV,
                           backend=backend)
    with ops.pallas_refusal(relation):
        return jax.jit(fn).lower(tx, ty, colg).compile()


@pytest.mark.parametrize("relation", OFFLOADED_RELATIONS)
def test_xla_relation_block_compiles(relation, one_chip):
    compiled = _compile_block(relation, "xla", one_chip)
    assert compiled.memory_analysis() is not None


def test_xla_completion_gather_compiles(one_chip):
    """TT completion on the 100^3 grid: 11,482,816 (segment, tet)
    appearances, too many for the combined i32 key, so the lexicographic
    resolve (``inv_key=None``) is the one compiled."""
    S, R, deg = 64, WIDTH["T"][0], ops.DEFAULT_DEG["TT"]
    K, P, n = 11_482_816, 4096, 1024
    args = (_sds((S, R, deg), one_chip), _sds((S, R), one_chip),
            _sds((K,), one_chip), _sds((K,), one_chip),
            _sds((K,), one_chip), None,
            _sds((P,), one_chip), _sds((P,), one_chip),
            _sds((P,), one_chip), _sds((n, 4), one_chip))
    compiled = cg._gather_union_xla.lower(
        *args, deg_out=deg, n_global=5_821_794).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("jit_name", ["across_successors", "boundary_mask"])
def test_whole_mesh_jit_fits_hbm_at_full_size(jit_name, one_chip):
    """The drivers' whole-mesh jits over completed TT at the 100^3 grid's
    5.8 M tets (Morse-Smale's successor assembly, the boundary-vertex
    mask). Laid out as (nt, deg, 4) they padded their minor dims to full
    lanes and asked for ~23 GB of HBM on a 16 GB v5e; they must stay far
    below."""
    from repro.algorithms.critical_points import _boundary_mask
    from repro.algorithms.morse_smale import _across_successors
    nt, nf, nv = 5_821_794, 11_702_394, 1_000_000
    M = _sds((nt, ops.DEFAULT_DEG["TT"]), one_chip)
    T = _sds((nt, 4), one_chip)
    if jit_name == "across_successors":
        lowered = _across_successors.lower(M, _sds((nt,), one_chip),
                                           _sds((nf, 3), one_chip), T)
    else:
        mask = jax.ShapeDtypeStruct((nv + 1,), jnp.bool_, sharding=one_chip)
        lowered = _boundary_mask.lower(M, T, mask)
    assert lowered.compile().memory_analysis().temp_size_in_bytes < 4e9


def test_pallas_counts_vv_compiles(one_chip):
    tt = _sds((B, 4, WIDTH["T"][0]), one_chip)
    sr.relation_counts_vv_pallas.lower(tt, nvl=NV).compile()


def test_pallas_counts_meet_compiles(one_chip):
    te = _sds((B, 2, WIDTH["E"][0]), one_chip)
    sr.relation_counts_meet_pallas.lower(te, te, nvl=NV).compile()


@pytest.mark.parametrize("relation", PALLAS_COMPILED)
def test_pallas_relation_block_compiles(relation, one_chip):
    compiled = _compile_block(relation, "pallas", one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("relation", PALLAS_REFUSED)
def test_pallas_relation_block_refused_names_relation(relation, one_chip):
    with pytest.raises(KernelCompileError, match=f"'{relation}'") as ei:
        _compile_block(relation, "pallas", one_chip)
    assert ei.value.relation == relation
    assert ei.value.__cause__ is not None     # the compiler's own error


def test_pallas_completion_gather_refused(one_chip):
    S, R, deg = 64, WIDTH["T"][0], ops.DEFAULT_DEG["TT"]
    K, P = 1 << 20, 4096
    args = (_sds((S, R, deg), one_chip), _sds((S, R), one_chip),
            _sds((1, K), one_chip), _sds((1, K), one_chip),
            _sds((1, K), one_chip), _sds((1, P), one_chip),
            _sds((1, P), one_chip), _sds((1, P), one_chip))
    with pytest.raises(KernelCompileError, match="completion gather"):
        with ops.pallas_refusal("completion gather"):
            cg._resolve_gather_pallas.lower(
                *args, K=K, interpret=False, block_pairs=512).compile()


# -- CPU checks (no topology) -------------------------------------------------


@pytest.fixture(scope="module")
def small_pre():
    sm = segment_mesh(structured_grid(6, 6, 6), capacity=16)
    return precondition(sm, relations=["VV", "VT"])


def test_pallas_backend_raises_in_engine_without_tpu(small_pre):
    """``backend="pallas"`` never interprets and never degrades: without a
    TPU the engine raises the named compile error, and no recovery arm
    fired."""
    assert jax.devices()[0].platform != "tpu"
    eng = RelationEngine(small_pre, ["VV"], backend="pallas", tune="off")
    with pytest.raises(KernelCompileError, match="'VV'"):
        eng.get("VV", 0)
    s = eng.stats
    assert s.retries == s.degraded_launches == s.breaker_trips == 0


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
def test_pallas_refusal_leaves_other_backends_alone(backend):
    """Only ``backend="pallas"`` maps compiler refusals: the same error under
    the interpreter (or xla) is a bug and propagates as itself."""
    with pytest.raises(ValueError, match="interpret mode"):
        with ops.pallas_refusal("VT", backend):
            raise ValueError("Only interpret mode is supported on CPU "
                             "backend.")


def test_pallas_refusal_maps_only_compiler_refusals():
    with pytest.raises(KernelCompileError, match="'VT'"):
        with ops.pallas_refusal("VT", "pallas"):
            raise NotImplementedError("Only 2D gather is supported")
    with pytest.raises(ValueError, match="table width"):
        with ops.pallas_refusal("VT", "pallas"):
            raise ValueError("table width 3 != 4")


def test_interpret_wrapper_error_is_not_a_refusal():
    """A shape bug on the interpret path surfaces as the wrapper's own
    error, not as a compile refusal of another backend."""
    tabX = jnp.zeros((2, 8, 1), jnp.int32)
    tabY = jnp.zeros((2, 16, 4), jnp.int32)
    colg = jnp.zeros((2, 16, 2), jnp.int32)      # 3-D column map: a bug
    with pytest.raises(ValueError, match="Block shape") as ei:
        ops.relation_block("VT", tabX, tabY, colg, 8,
                           backend="pallas_interpret")
    assert not isinstance(ei.value, KernelCompileError)


def _chip_smoke():
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_main_path_check_small_mesh():
    smoke = _chip_smoke()
    lines = []
    want = smoke.check_main_path((8, 8, 8), seed=0, warm=True,
                                 log=lines.append)
    assert want["euler"] == 1        # a grid is a ball
    assert any("bit-identical to explicit" in ln for ln in lines)


def test_chip_smoke_sharded_check_small_mesh():
    smoke = _chip_smoke()
    lines = []
    smoke.check_sharded((16, 6, 6), seed=1, shards=2, log=lines.append)
    assert any("bit-identical to shards=1" in ln for ln in lines)


def test_chip_smoke_refuses_without_tpu(capsys):
    smoke = _chip_smoke()
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_chip_smoke_refuses_fault_injection(monkeypatch, capsys):
    smoke = _chip_smoke()
    monkeypatch.setenv("REPRO_FAULT_SPEC", "launch:p=0.5")
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_respects_env(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []                     # JAX's own handling stands


def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.enable() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
