"""The sharded serve-time data plane: the batched offload hot path
(``DetectionsBatch`` scoring, ``match_batch``, ``extract_features_batch``,
the fused estimator MLP) on a :func:`repro.launch.mesh.make_fleet_mesh`
device mesh via ``shard_map``, with **streams as the sharded axis** —
numerically identical to the single-device path.

Bit-exactness is a hard contract here (the fleet runtime compares shards'
decisions against single-device traces), and it is not free: XLA:CPU
compiles the ``iou_matrix`` Pallas grid loop differently for a
single-iteration batch grid than for a multi-iteration one (a 1-ulp
FMA/vectorization difference between ``grid_b == 1`` and ``grid_b >= 2``
programs; within a regime, runs agree bit-for-bit at the same tile shape).
So the sharded matcher mirrors :func:`repro.detection.batch.match_batch`'s
tile selection computed from the *global* batch, and pads each shard-local
block so its batch grid falls in the same regime as the global call's:

* global grid_b == 1 (small batches): every shard block pads to one
  ``tile_b`` tile — same grid, same tile shape, bit-identical.
* global grid_b >= 2: shard blocks pad to at least two ``tile_b`` tiles,
  landing in the multi-tile compilation regime — bit-identical again.

The regime machinery only matters on the Pallas paths; on the auto
``"reference"`` path (CPU default, see ``repro.kernels.dispatch``) the IoU
is an elementwise ``vmap`` over box pairs, which is trivially shard-safe
and needs no extra padding.

Downstream of the IoU kernel, greedy matching (``_match_inputs`` /
``_greedy_match``) and the feature/MLP kernels are comparisons, sorts and
per-image/per-row arithmetic, which the equivalence property in
``tests/test_sharding.py`` pins down across ragged shard boundaries.

Everything degrades to the exact single-device functions on a 1-device
mesh, so code written against the plane runs unchanged on laptop CI.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.features import _features_kernel, extract_features_batch
from repro.detection.batch import (
    DetectionsBatch,
    GroundTruthBatch,
    MatchResult,
    _greedy_match,
    _match_inputs,
    _pad_dim,
    match_batch,
)
from repro.kernels.estimator_mlp import estimator_mlp
from repro.kernels.iou_matrix.ops import iou_matrix_batch, resolve_path
from repro.launch.mesh import make_fleet_mesh
from repro.obs.jit_stats import count_call


def _ceil_to(n: int, multiple: int) -> int:
    return -(-max(n, 1) // multiple) * multiple


class FleetPlane:
    """The offload data plane on a 1-D ``"shard"`` device mesh.

    Parameters
    ----------
    mesh : jax.sharding.Mesh or None
        An existing 1-axis mesh (typically from ``make_fleet_mesh``);
        ``None`` builds one over ``n_shards`` visible devices.
    n_shards : int or None
        Device count for the constructed mesh (``None`` = all visible);
        ignored when ``mesh`` is given.
    """

    def __init__(
        self, mesh: Optional[Mesh] = None, *, n_shards: Optional[int] = None
    ):
        self.mesh = mesh if mesh is not None else make_fleet_mesh(n_shards)
        axes = tuple(self.mesh.axis_names)
        if len(axes) != 1:
            raise ValueError(f"fleet mesh must have exactly one axis, got {axes}")
        self.axis = axes[0]

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    def shard_sizes(self, n: int) -> Tuple[int, int]:
        """(rows per shard, padded total) for ``n`` items over the mesh —
        the last shard is ragged; padding fills it."""
        per = -(-n // self.n_devices)
        return per, per * self.n_devices

    def _shard1d(self, fn, n_in: int, n_out: int):
        """``shard_map`` ``fn`` with every input/output sharded on axis 0."""
        return jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(P(self.axis),) * n_in,
            out_specs=(P(self.axis),) * n_out if n_out > 1 else P(self.axis),
            check_vma=False,
        )

    # ------------------------------------------------------------- scoring

    def score(self, engine, features: np.ndarray) -> np.ndarray:
        """Batched reward estimates with rows sharded over the mesh —
        bit-identical to ``engine.score``.  Non-fused reward models (and
        1-device meshes) fall through to the engine's own path."""
        x = np.asarray(features, np.float32)
        model = engine.reward_model
        if self.n_devices == 1 or not getattr(model, "fused", False):
            return np.asarray(engine.score(features=x))
        # the shard_map closure below is rebuilt per call (params close
        # over fresh arrays), so retraces can't be read off a stable jit
        # object — count dispatches instead
        count_call("fleet_plane.score")
        est = model.estimator
        if model.config.standardize:
            x = (x - est._mu) / est._sigma
        p = est.params
        w1, b1 = p["layer0"]["w"], p["layer0"]["b"]
        w2, b2 = p["layer1"]["w"][:, 0], p["layer1"]["b"][0]
        interpret = model.interpret
        B = x.shape[0]
        _, total = self.shard_sizes(max(B, 1))
        xp = np.zeros((total, x.shape[1]), np.float32)
        xp[:B] = x

        def local(xs):
            return estimator_mlp(xs, w1, b1, w2, b2, interpret=interpret)

        out = self._shard1d(local, 1, 1)(jnp.asarray(xp))
        return np.asarray(out)[:B]

    def score_detections(self, engine, batch: DetectionsBatch) -> np.ndarray:
        """Device-resident boxes→estimates scoring with images sharded over
        the mesh — bit-identical to ``engine.score_device(batch)`` (and so
        to the composed ``engine.score`` route).

        The per-image feature kernel (the parallel bulk of the pipeline)
        runs sharded; the standardize + MLP head then runs as ONE
        replicated dispatch on the cropped device-resident features — the
        same trace the single-device path executes.  Fusing the head into
        the shard_map body would put XLA:CPU's gemm at shard-local row
        counts, which compiles to a different reduction schedule than the
        global call at some shapes (1-ulp drift) — the split keeps the
        plane's bit-exactness contract without a host exit between stages.
        Engines without the fused MLP + box feature extractor, and
        1-device meshes, fall through to the engine's own device path."""
        fx = engine.feature_extractor
        model = engine.reward_model
        fused = (
            getattr(model, "fused", False)
            and hasattr(model, "predict_device")
            and all(hasattr(fx, a) for a in ("num_classes", "top_k", "image_size"))
        )
        if self.n_devices == 1 or not fused:
            return np.asarray(engine.score_device(batch))
        count_call("fleet_plane.score_detections")
        B = len(batch)
        _, total = self.shard_sizes(max(B, 1))
        padded = batch.pad_images(total)
        boxes, scores = padded.boxes, padded.scores
        classes, mask = padded.classes, padded.mask
        top_k = int(fx.top_k)
        if padded.max_boxes < top_k:  # the kernel slices a fixed top_k window
            pad = top_k - padded.max_boxes
            boxes = np.pad(boxes, ((0, 0), (0, pad), (0, 0)))
            scores = np.pad(scores, ((0, 0), (0, pad)))
            classes = np.pad(classes, ((0, 0), (0, pad)), constant_values=-1)
            mask = np.pad(mask, ((0, 0), (0, pad)))
        image_size = jnp.float32(fx.image_size)
        num_classes = int(fx.num_classes)

        def local(b, s, c, m):
            return _features_kernel(b, s, c, m, image_size, num_classes, top_k)

        f = self._shard1d(local, 4, 1)(
            jnp.asarray(boxes), jnp.asarray(scores),
            jnp.asarray(classes), jnp.asarray(mask),
        )
        # gather the cropped features onto one device before the head: a
        # jit over still-sharded inputs auto-partitions the gemm back to
        # shard-local row counts (the drift the split exists to avoid)
        f = jax.device_put(f[:B], self.mesh.devices.flat[0])
        return np.asarray(model.predict_device(f))

    # ------------------------------------------------------------ matching

    def match(
        self,
        det: DetectionsBatch,
        gt: GroundTruthBatch,
        iou_thresholds: Sequence[float] = (0.5,),
        *,
        interpret: Union[None, bool, str] = None,
        tile_b: int = 8,
        tile_n: int = 128,
        tile_m: int = 128,
    ) -> MatchResult:
        """Batched COCO greedy matching with images sharded over the mesh
        — bit-identical to single-device :func:`match_batch` (see the
        module docstring for the grid-regime padding that guarantees it)."""
        if len(det) != len(gt):
            raise ValueError(f"batch size mismatch: {len(det)} dets vs {len(gt)} gts")
        if self.n_devices == 1:
            return match_batch(
                det, gt, iou_thresholds, interpret=interpret,
                tile_b=tile_b, tile_n=tile_n, tile_m=tile_m,
            )
        B = len(det)
        interp = resolve_path(interpret)
        if interp == "interpret":
            # mirror match_batch's interpreter-mode tile shrink, computed
            # from the GLOBAL batch — shard-local tiles must not differ
            tile_n = min(tile_n, _pad_dim(det.max_boxes))
            tile_m = min(tile_m, _pad_dim(gt.max_boxes))
            tile_b = min(64, _pad_dim(B))
        per, total = self.shard_sizes(B)
        det_p, gt_p = det.pad_images(total), gt.pad_images(total)
        if interp == "reference":
            # elementwise vmap IoU: no grid regimes, no extra padding
            local_rows = per
        else:
            grid_ref = _ceil_to(B, tile_b) // tile_b
            # shard blocks must compile in the single-device call's batch-grid
            # regime: one tile when the global grid has one, >= 2 tiles otherwise
            local_rows = _ceil_to(per, tile_b) if grid_ref == 1 else max(
                _ceil_to(per, tile_b), 2 * tile_b
            )
        thresholds = jnp.asarray(iou_thresholds, jnp.float32)

        def local(d_boxes, d_scores, d_classes, d_mask, g_boxes, g_classes, g_mask):
            pad = local_rows - d_boxes.shape[0]
            if pad:
                widths = ((0, pad),)
                d_boxes = jnp.pad(d_boxes, widths + ((0, 0), (0, 0)))
                g_boxes = jnp.pad(g_boxes, widths + ((0, 0), (0, 0)))
                d_scores = jnp.pad(d_scores, widths + ((0, 0),))
                d_classes = jnp.pad(
                    d_classes, widths + ((0, 0),), constant_values=-1
                )
                g_classes = jnp.pad(
                    g_classes, widths + ((0, 0),), constant_values=-1
                )
                d_mask = jnp.pad(d_mask, widths + ((0, 0),))
                g_mask = jnp.pad(g_mask, widths + ((0, 0),))
            iou = iou_matrix_batch(
                d_boxes, g_boxes,
                tile_b=tile_b, tile_n=tile_n, tile_m=tile_m, interpret=interp,
            )
            masked, order = _match_inputs(
                d_scores, d_classes, d_mask, g_classes, g_mask, iou
            )
            tp, mj = _greedy_match(masked, order, thresholds)
            return tp[:per], mj[:per]

        tp, mj = self._shard1d(local, 7, 2)(
            jnp.asarray(det_p.boxes), jnp.asarray(det_p.scores),
            jnp.asarray(det_p.classes), jnp.asarray(det_p.mask),
            jnp.asarray(gt_p.boxes), jnp.asarray(gt_p.classes),
            jnp.asarray(gt_p.mask),
        )
        return MatchResult(
            tp=np.asarray(tp)[:B],
            match_gt=np.asarray(mj, np.int32)[:B],
            iou_thresholds=tuple(float(t) for t in iou_thresholds),
        )

    # ------------------------------------------------------------ features

    def extract_features(
        self,
        batch: DetectionsBatch,
        num_classes: int,
        top_k: int = 25,
        image_size: float = 1.0,
    ) -> np.ndarray:
        """The weak-output feature kernel with images sharded over the mesh
        — bit-identical to :func:`extract_features_batch`."""
        if self.n_devices == 1:
            return extract_features_batch(batch, num_classes, top_k, image_size)
        B = len(batch)
        _, total = self.shard_sizes(max(B, 1))
        padded = batch.pad_images(total)
        boxes, scores = padded.boxes, padded.scores
        classes, mask = padded.classes, padded.mask
        if padded.max_boxes < top_k:  # the kernel slices a fixed top_k window
            pad = top_k - padded.max_boxes
            boxes = np.pad(boxes, ((0, 0), (0, pad), (0, 0)))
            scores = np.pad(scores, ((0, 0), (0, pad)))
            classes = np.pad(classes, ((0, 0), (0, pad)), constant_values=-1)
            mask = np.pad(mask, ((0, 0), (0, pad)))

        def local(b, s, c, m):
            return _features_kernel(
                b, s, c, m, jnp.float32(image_size), int(num_classes), int(top_k)
            )

        out = self._shard1d(local, 4, 1)(
            jnp.asarray(boxes), jnp.asarray(scores),
            jnp.asarray(classes), jnp.asarray(mask),
        )
        return np.asarray(out)[:B]
