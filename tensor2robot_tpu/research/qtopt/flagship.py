"""The flagship measurement configuration of the QT-Opt grasping critic.

One shared constructor so every surface that compiles or times the
critic outside a gin file (the described-v5e compiles of
tests/test_mosaic_lowering.py, `obs/forge.py`'s smoke plan) builds the
SAME network: reference-scale Grasping44 — the 16-conv BN tower (stem + 6+6+3,
reference /root/reference/research/qtopt/networks.py:299-615) at
472x472x3 with named grasp-param blocks, bfloat16 compute and EMA —
exactly what `research/qtopt/configs/train_qtopt.gin` trains. The small
32-px smoke critic the CPU tests use is the same
constructor with `smoke=True`, asked for by name and never chosen from
the platform.
"""

from __future__ import annotations

from typing import Optional

from tensor2robot_tpu.research.qtopt import models as qtopt_models

IMAGE_SIZE = 472
ACTION_SIZE = 5
GRASP_PARAM_NAMES = {"world_vector": (0, 3), "vertical_rotation": (3, 2)}


def make_flagship_model(device_platform: str, remat: bool = False,
                        space_to_depth: bool = False,
                        image_size: Optional[int] = None,
                        smoke: bool = False):
  """Reference-scale Grasping44 critic, or with `smoke=True` the small
  smoke critic. `space_to_depth` folds the stem per
  Grasping44.space_to_depth (exact math, 4x the stem's MXU lane
  utilization) — off by default; no cell runs it (ROADMAP D3). `image_size` overrides
  the reference 472 (reduced-scale CI compile twins stay on this one
  constructor instead of hand-copying it)."""
  full = not smoke
  return qtopt_models.QTOptModel(
      image_size=(image_size if image_size is not None
                  else (IMAGE_SIZE if full else 32)),
      device_type=device_platform,
      network="grasping44" if full else "small",
      action_size=ACTION_SIZE if full else 4,
      grasp_param_names=GRASP_PARAM_NAMES if full else None,
      space_to_depth=space_to_depth and full,
      use_bfloat16=full, use_ema=True, remat=remat)
