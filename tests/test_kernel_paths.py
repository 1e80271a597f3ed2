"""Every kernel entry point takes the path names of :mod:`repro.kernels.dispatch`:
its two CPU paths agree, and any other name is refused."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.features import feature_dim
from repro.detection.batch import DetectionsBatch
from repro.kernels.estimator_mlp import estimator_mlp
from repro.kernels.iou_matrix import iou_matrix_batch
from repro.kernels.score_pipeline import score_pipeline
from repro.video import WEAK_PROFILE, generate_clip, synthesize_detections, track_clip

RNG = np.random.default_rng(0)
XY = RNG.uniform(0, 50, (3, 5, 12, 2))
BOXES = np.concatenate([XY, XY + RNG.uniform(1, 20, XY.shape)], -1).astype(np.float32)
F, H = feature_dim(5, 8), 16
W = {k: jnp.asarray(RNG.normal(0, 0.2, s), jnp.float32)
     for k, s in dict(x=(37, F), w1=(F, H), b1=H, w2=H, b2=(), mu=F).items()}
PARAMS = dict(W, sigma=1 + jnp.abs(W["mu"]))
DETS = DetectionsBatch(boxes=BOXES[2], scores=RNG.uniform(size=(5, 12)),
                       classes=RNG.integers(0, 5, (5, 12)), mask=RNG.uniform(size=(5, 12)) < 0.6)
CLIP = synthesize_detections(generate_clip(2, 8, seed=3), WEAK_PROFILE, seed=4)

#: each entry point on a path, and the (atol, rtol) its existing tests use
CASES = {
    "iou_matrix_batch": (lambda p: iou_matrix_batch(BOXES[0], BOXES[1, :, :6], interpret=p), 1e-6, 1e-7),
    "estimator_mlp": (lambda p: estimator_mlp(*map(W.get, ("x", "w1", "b1", "w2", "b2")), interpret=p),
                      1e-5, 1e-7),
    "score_pipeline": (lambda p: score_pipeline(DETS, PARAMS, num_classes=5, top_k=8, path=p), 2e-6, 1e-7),
    "track_clip": (lambda p: vars(track_clip(CLIP, interpret=p)), 1e-5, 1e-5),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_every_kernel_takes_one_path_vocabulary(kernel):
    run, atol, rtol = CASES[kernel]
    ref, interp = ({"out": r} if not isinstance(r, dict) else r for r in map(run, ("reference", "interpret")))
    assert ref.keys() == interp.keys()
    for key, want in ref.items():
        want, got = np.asarray(want), np.asarray(interp[key])
        if np.issubdtype(want.dtype, np.floating):
            np.testing.assert_allclose(got, want, atol=atol, rtol=rtol, err_msg=key)
        else:
            np.testing.assert_array_equal(got, want, err_msg=key)
    with pytest.raises(ValueError, match="unknown kernel path"):
        run("pallas")
