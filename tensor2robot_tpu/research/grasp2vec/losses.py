"""Grasp2Vec embedding-arithmetic loss family.

Reference: /root/reference/research/grasp2vec/losses.py:29-304 —
L2/cosine arithmetic losses (masked by grasp success), semihard triplet
and bidirectional n-pairs objectives (plus the multilabel variant for
failed grasps), keypoint quadrant accuracy for the Shapes dataset,
norm-matching and send-to-zero regularizers, and the spatial softmax
response / TY ratio loss over scene feature maps.

All functions are pure jnp with static shapes: the reference's
`tf.dynamic_partition` + `tf.cond` masking is replaced by weighted means
(`sum(x*m)/max(sum(m),1)`), which XLA fuses and which equal the reference
value for every non-empty mask and 0 for the empty one.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers import tec as tec_lib

__all__ = [
    "l2_arithmetic_loss", "cosine_arithmetic_loss", "triplet_loss",
    "npairs_loss_bidirectional", "npairs_loss_multilabel",
    "keypoint_accuracy", "send_to_zero_loss", "match_norms_loss",
    "get_softmax_response", "ty_loss", "heatmap_keypoints",
]


def _masked_mean(values: jnp.ndarray,
                 mask: Optional[jnp.ndarray]) -> jnp.ndarray:
  if mask is None:
    return values.mean()
  mask = mask.reshape(values.shape).astype(values.dtype)
  return jnp.sum(values * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _l2_normalize(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
  return x / jnp.maximum(jnp.linalg.norm(x, axis=axis, keepdims=True), 1e-12)


def l2_arithmetic_loss(pregrasp_embedding, goal_embedding,
                       postgrasp_embedding, mask=None) -> jnp.ndarray:
  """Masked mean of ||pre - goal - post||^2 (reference :29-52)."""
  raw = pregrasp_embedding - goal_embedding - postgrasp_embedding
  distances = jnp.sum(raw ** 2, axis=1)
  return _masked_mean(distances, mask)


def cosine_arithmetic_loss(pregrasp_embedding, goal_embedding,
                           postgrasp_embedding, mask=None) -> jnp.ndarray:
  """Masked mean cosine distance between normalize(pre - post) and
  normalize(goal) (reference :80-107)."""
  pair_a = _l2_normalize(pregrasp_embedding - postgrasp_embedding)
  pair_b = _l2_normalize(goal_embedding)
  distances = 1.0 - jnp.sum(pair_a * pair_b, axis=1)
  return _masked_mean(distances, mask)


def triplet_loss(pregrasp_embedding, goal_embedding, postgrasp_embedding,
                 margin: float = 3.0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
  """Semihard triplet over {normalize(pre-post), normalize(goal)} pairs
  sharing per-example labels (reference :56-77). Returns
  (loss, pairs, labels) like the reference."""
  pair_a = _l2_normalize(pregrasp_embedding - postgrasp_embedding)
  pair_b = _l2_normalize(goal_embedding)
  n = pregrasp_embedding.shape[0]
  labels = jnp.tile(jnp.arange(n), 2)
  pairs = jnp.concatenate([pair_a, pair_b], axis=0)
  loss = tec_lib.triplet_semihard_loss(
      pairs, labels, margin=margin, distance="euclidean")
  return loss, pairs, labels


def npairs_loss_bidirectional(pregrasp_embedding, goal_embedding,
                              postgrasp_embedding,
                              non_negativity_constraint: bool = False
                              ) -> jnp.ndarray:
  """n-pairs in both anchor orders over (pre - post, goal)
  (reference :159-185)."""
  pair_a = pregrasp_embedding - postgrasp_embedding
  if non_negativity_constraint:
    pair_a = jax.nn.relu(pair_a)
  pair_b = goal_embedding
  loss_1 = tec_lib.npairs_loss(pair_a, pair_b)
  loss_2 = tec_lib.npairs_loss(pair_b, pair_a)
  return loss_1 + loss_2


def npairs_loss_multilabel(pregrasp_embedding, goal_embedding,
                           postgrasp_embedding, grasp_success
                           ) -> jnp.ndarray:
  """n-pairs with failed grasps collapsed onto a shared 'nothing grasped'
  class (reference :188-219): example i gets label i+... only when its
  grasp succeeded, else label 0, and targets spread probability over all
  examples sharing a label."""
  pair_a = pregrasp_embedding - postgrasp_embedding
  pair_b = goal_embedding
  n = pregrasp_embedding.shape[0]
  success = jnp.reshape(grasp_success, (n,)).astype(jnp.int32)
  labels = jnp.arange(n, dtype=jnp.int32) * success

  def one_direction(anchor, positive):
    logits = anchor @ positive.T
    same = (labels[:, None] == labels[None, :]).astype(jnp.float32)
    targets = same / same.sum(-1, keepdims=True)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    return -(targets * log_probs).sum(-1).mean()

  return one_direction(pair_a, pair_b) + one_direction(pair_b, pair_a)


# Host constant on purpose: a module-level `jnp.array` initializes the JAX
# backend at import time — ANY import of this module would then take
# the chip. numpy converts to a
# device constant at trace time instead (graftlint: import-time-backend).
_QUADRANT_CENTERS = np.array(
    [[0.5, -0.5], [-0.5, -0.5], [0.5, 0.5], [-0.5, 0.5]], np.float32)


def keypoint_accuracy(keypoints, labels
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """Quadrant accuracy + sigmoid CE of spatial-softmax keypoints against
  integer quadrant labels (reference :110-135, Shapes dataset only)."""
  keypoints = jnp.reshape(keypoints, (-1, 2))
  labels = jnp.reshape(labels, (-1,)).astype(jnp.int32)
  logits = keypoints @ _QUADRANT_CENTERS.T
  correct = (jnp.argmax(logits, axis=1) == labels).astype(jnp.float32)
  one_hot = jax.nn.one_hot(labels, 4)
  ce = jnp.maximum(logits, 0) - logits * one_hot + jnp.log1p(
      jnp.exp(-jnp.abs(logits)))
  return correct.mean(), ce.mean()


def send_to_zero_loss(tensor, mask=None) -> jnp.ndarray:
  """Masked mean L2 norm (reference :138-156)."""
  return _masked_mean(jnp.linalg.norm(tensor, axis=1), mask)


def match_norms_loss(anchor_tensors, paired_tensors) -> jnp.ndarray:
  """Pushes paired-tensor norms toward (stop-gradient) anchor norms
  (reference :222-238). Scaling pinned by the executed reference:
  tf.nn.l2_loss is a scalar sum(x^2)/2 over the BATCH (the reference's
  outer reduce_mean is a no-op on that scalar), so this is a batch sum,
  not a mean."""
  anchor_norms = jax.lax.stop_gradient(
      jnp.linalg.norm(anchor_tensors, axis=1))
  paired_norms = jnp.linalg.norm(paired_tensors, axis=1)
  return 0.5 * jnp.sum((anchor_norms - paired_norms) ** 2)


def get_softmax_response(goal_embedding, scene_spatial
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """(max heatmap response, max softmax mass) of a goal embedding against
  a spatial feature map (reference _GetSoftMaxResponse :241-266)."""
  heatmap = jnp.einsum("bhwd,bd->bhw", scene_spatial, goal_embedding)
  flat = heatmap.reshape(heatmap.shape[0], -1)
  max_heat = flat.max(axis=1)
  max_soft = jax.nn.softmax(flat, axis=1).max(axis=1)
  return max_heat, max_soft


def ty_loss(pregrasp_spatial, postgrasp_spatial,
            goal_embedding) -> jnp.ndarray:
  """Likelihood-ratio localization loss: the goal should respond more in
  the pregrasp scene than the postgrasp scene (reference :269-303)."""
  pre = _l2_normalize(pregrasp_spatial)
  post = _l2_normalize(postgrasp_spatial)
  goal = _l2_normalize(goal_embedding)[:, None, None, :]
  pre_max = jnp.sum(pre * goal, axis=-1).max(axis=(1, 2))
  post_max = jnp.sum(post * goal, axis=-1).max(axis=(1, 2))
  return jnp.mean(post_max - pre_max)


def heatmap_keypoints(heatmap: jnp.ndarray) -> jnp.ndarray:
  """Spatial soft-argmax of a [B, H, W] heatmap -> [B, 2] (x, y) in
  [-1, 1], the keypoint parameterization `keypoint_accuracy` scores."""
  b, h, w = heatmap.shape
  probs = jax.nn.softmax(heatmap.reshape(b, -1), axis=-1).reshape(b, h, w)
  ys = jnp.linspace(-1.0, 1.0, h)
  xs = jnp.linspace(-1.0, 1.0, w)
  y = jnp.sum(probs.sum(axis=2) * ys[None, :], axis=1)
  x = jnp.sum(probs.sum(axis=1) * xs[None, :], axis=1)
  return jnp.stack([x, y], axis=-1)
