"""Compile the main path's Pallas kernels for a TPU v5e without a chip.

The TPU compiler is installed with jaxlib and compiles for a described
``v5e:2x2`` topology.  Interpret-mode tests cannot see what it refuses —
a block that overflows the 16 MiB scoped VMEM of a core, a slice that is
not tile-aligned — so each kernel of the offload path is compiled here at
the widths the deployable engine runs: 8 classes, top-25 boxes
(387 features), one hidden layer of 128, the engine's ``tile_b``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several pytest workers
every worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.features import feature_dim
from repro.kernels.estimator_mlp.ops import _estimator_mlp_pallas
from repro.kernels.iou_matrix.ops import _iou_matrix_batch
from repro.kernels.score_pipeline.ops import (
    PACKED_SLOT_BYTES,
    _score_packed,
    _score_pipeline_pallas,
)
from repro.video import track as track_mod

HIDDEN = 128
ENGINE_TILE_B = 128  # score_pipeline's tile_b as OffloadEngine.score_device calls it


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(one_chip, *specs):
    return tuple(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in specs
    )


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "num_classes,top_k,batch",
    [(8, 25, 256), (8, 25, 1024), (80, 100, 1024)],
)
def test_fused_score_kernel_compiles(one_chip, no_compile_cache, num_classes, top_k, batch):
    """The fused boxes→estimates kernel at the engine's tile; the last case
    is a COCO-shaped head (80 classes, 100 detections)."""
    f32 = jnp.float32
    F = feature_dim(num_classes, top_k)
    k_in = top_k + 5  # the detector emits more boxes than the top-k window
    args = _shapes(
        one_chip,
        ((batch, k_in, 4), f32), ((batch, k_in), f32),
        ((batch, k_in), jnp.int32), ((batch, k_in), jnp.bool_),
        ((F, HIDDEN), f32), ((HIDDEN,), f32), ((HIDDEN,), f32), ((), f32),
        ((F,), f32), ((F,), f32), ((), f32),
    )
    compiled = _score_pipeline_pallas.lower(
        *args, num_classes=num_classes, top_k=top_k, tile_b=ENGINE_TILE_B,
        path="compiled",
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("batch", [1, 64])
def test_packed_score_entry_compiles(one_chip, no_compile_cache, batch):
    """The packed host-block entry (one ``uint8`` buffer unpacked on the
    device, then the fused kernel) at the COCO head: 80 classes, 100
    detection slots, top-100, a ragged and a full ``micro_batch`` chunk."""
    f32 = jnp.float32
    num_classes, top_k, slots = 80, 100, 100
    F = feature_dim(num_classes, top_k)
    args = _shapes(
        one_chip,
        ((batch, PACKED_SLOT_BYTES * slots), jnp.uint8),
        ((F, HIDDEN), f32), ((HIDDEN,), f32), ((HIDDEN,), f32), ((), f32),
        ((F,), f32), ((F,), f32), ((), f32),
    )
    compiled = _score_packed.lower(
        *args, num_classes=num_classes, top_k=top_k, tile_b=ENGINE_TILE_B,
        path="compiled",
    ).compile()
    _assert_kernel(compiled)


def test_estimator_mlp_compiles(one_chip, no_compile_cache):
    f32 = jnp.float32
    F = feature_dim(8, 25)
    args = _shapes(
        one_chip, ((512, F), f32), ((F, HIDDEN), f32), ((HIDDEN,), f32),
        ((HIDDEN,), f32), ((), f32),
    )
    compiled = _estimator_mlp_pallas.lower(*args, tile_b=128, interpret=False).compile()
    _assert_kernel(compiled)


def test_iou_matrix_batch_compiles_at_match_tiles(one_chip, no_compile_cache):
    """``match_batch``'s compiled tiles over 64 images of 100×100 boxes."""
    args = _shapes(one_chip, ((64, 100, 4), jnp.float32), ((64, 100, 4), jnp.float32))
    compiled = _iou_matrix_batch.lower(
        *args, tile_b=8, tile_n=128, tile_m=128, interpret=False
    ).compile()
    _assert_kernel(compiled)


def test_tracker_scan_compiles_with_compiled_iou(one_chip, no_compile_cache):
    """The tracker's whole-clip ``lax.scan`` with the IoU kernel compiled
    inside each step: 8 streams, 64 frames, the default track/det slots."""
    cfg = track_mod.TrackerConfig()
    B, T, K, N = 8, 64, cfg.max_dets, cfg.max_tracks
    state = _shapes(
        one_chip,
        ((B, N, 4), jnp.float32), ((B, N, 4), jnp.float32),
        ((B, N), jnp.float32), ((B, N), jnp.int32), ((B, N), jnp.int32),
        ((B, N), jnp.int32), ((B, N), jnp.bool_), ((B,), jnp.int32),
    )
    frames = _shapes(
        one_chip,
        ((T, B, K, 4), jnp.float32), ((T, B, K), jnp.float32),
        ((T, B, K), jnp.int32), ((T, B, K), jnp.bool_),
    )
    compiled = track_mod._scan_jit.lower(state, frames, cfg, False).compile()
    _assert_kernel(compiled)
    # the state shapes above are the tracker's own
    assert [s.shape for s in state] == [
        np.shape(a) for a in jax.eval_shape(lambda: track_mod._init_state(B, cfg))
    ]
