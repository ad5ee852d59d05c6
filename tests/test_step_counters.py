"""Step metrics a model names as counters ride the stepstats record: read at
the cadence, after the barrier, from the dispatch's own metrics."""

import numpy as np
import pytest

from tensor2robot_tpu.obs import stepstats as stepstats_lib


def _recorder(**kwargs):
  return stepstats_lib.StepStatsRecorder(
      batch_size=2, barrier=lambda state: np.zeros(()), device_gauges=False,
      **kwargs)


def _step(recorder, step, metrics, num_steps=1):
  recorder.before_dispatch()
  recorder.after_dispatch()
  recorder.end_step(step, state=None, num_steps=num_steps, metrics=metrics)


def test_counters_join_the_record_at_the_cadence():
  recorder = _recorder(every_n_steps=2, counter_prefixes=("moe_",))
  seen = []
  recorder.add_observer(lambda step, record: seen.append((step, record)))
  recorder.start()
  _step(recorder, 1, {"loss": 3.0, "moe_rows_held/layer_0": 11.0})
  assert seen == []                       # no record, nothing fetched
  _step(recorder, 2, {"loss": 2.0, "moe_rows_held/layer_0": 12.0,
                      "moe_rows_dropped/layer_0": np.float32(0.0)})
  (step, record), = seen
  assert step == 2
  assert record["moe_rows_held/layer_0"] == 12.0   # the last step's
  assert record["moe_rows_dropped/layer_0"] == 0.0
  assert "loss" not in record and "data_wait_ms" in record


def test_a_loops_stacked_metrics_give_the_last_step():
  recorder = _recorder(every_n_steps=1, counter_prefixes=("moe_",))
  recorder.start()
  _step(recorder, 4, {"moe_buffer_fill/layer_1": np.asarray([0.4, 0.5, 0.6])},
        num_steps=3)
  (_, record), = recorder.drain()
  assert record["moe_buffer_fill/layer_1"] == pytest.approx(0.6)


@pytest.mark.parametrize("kwargs", [{}, {"counter_prefixes": ("moe_",)}])
def test_without_prefixes_or_metrics_the_record_is_as_it_was(kwargs):
  recorder = _recorder(every_n_steps=1, **kwargs)
  recorder.start()
  metrics = {"moe_rows_held/layer_0": 5.0} if not kwargs else None
  _step(recorder, 1, metrics)
  (_, record), = recorder.drain()
  assert not [k for k in record if k.startswith("moe_")]


def test_models_name_no_counters_unless_they_say_so():
  from tensor2robot_tpu.models import hybrid_lm
  from tensor2robot_tpu.models import sequence_model

  assert hybrid_lm.HybridDecoderLM(device_type="cpu").step_counter_prefixes \
      == ("moe_",)
  plain = sequence_model.SequenceRegressionModel(device_type="cpu")
  assert getattr(plain, "step_counter_prefixes", ()) == ()


def test_counters_are_named_by_the_layers_own_numbers():
  """Where only some layers have experts (`MEMEM*EME`: layers 1, 3, 6, 8),
  a counter carries the number of its layer, not its place among the expert
  layers, and the record holds those and no others."""
  import jax.numpy as jnp

  from tensor2robot_tpu.models import hybrid_lm

  kinds = ("mamba", "experts", "mamba", "experts", "mamba", "attention",
           "experts", "mamba", "experts")
  model = hybrid_lm.HybridDecoderLM(device_type="cpu", layer_types=kinds,
                                    sequence_length=8, loss_chunk=8)
  outputs = {name: jnp.arange(4.0) + 10 * i
             for i, name in enumerate(hybrid_lm.COUNTERS)}
  outputs.update(hidden=jnp.zeros((1, 8, 64)), head=jnp.zeros((64, 1024)))
  labels = {"targets": jnp.zeros((1, 8), jnp.int32),
            "weight": jnp.ones((1, 1))}
  _, scalars = model.model_train_fn({}, labels, outputs, "train")
  assert sorted(scalars) == sorted(
      f"{name}/layer_{i}" for name in hybrid_lm.COUNTERS
      for i in (1, 3, 6, 8))
  assert float(scalars["moe_rows_held/layer_6"]) == 2.0      # the third
  assert float(scalars["moe_buffer_fill/layer_8"]) == 13.0   # the fourth
  recorder = _recorder(every_n_steps=1,
                       counter_prefixes=model.step_counter_prefixes)
  recorder.start()
  _step(recorder, 1, dict(scalars, loss=1.0))
  (_, record), = recorder.drain()
  assert sorted(k for k in record if k.startswith("moe_")) == sorted(scalars)
