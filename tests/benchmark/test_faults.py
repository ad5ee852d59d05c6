"""The rest of a run, driven past the harness's look for a chip, with the
timed path broken underneath: `correct` has to come out false, once for each
fault a training cell can have (a step that returns its state unchanged; half
of the batch left out, the mean taken over the rest)."""

import pytest

from tests.benchmark.rehearse import CELLS, rehearse


def _break_step(monkeypatch, wrap):
  from tensor2robot_tpu.parallel import train_step as ts

  build = ts._build_step_fn

  def broken(model):
    return wrap(build(model))

  monkeypatch.setattr(ts, "_build_step_fn", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
    capsys, monkeypatch, cell):
  def wrap(step_fn):
    def step(state, features, labels):
      new_state, metrics = step_fn(state, features, labels)
      return state.replace(step=new_state.step), metrics
    return step

  _break_step(monkeypatch, wrap)
  result, _ = rehearse(capsys, cell)
  assert result["correct"] is False
  assert result["checks"]["param_change"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch,
                                                   cell):
  import jax

  def wrap(step_fn):
    def step(state, features, labels):
      half = lambda tree: jax.tree_util.tree_map(
          lambda x: x[:x.shape[0] // 2], tree)
      return step_fn(state, half(features), half(labels))
    return step

  _break_step(monkeypatch, wrap)
  result, _ = rehearse(capsys, cell)
  assert result["correct"] is False


