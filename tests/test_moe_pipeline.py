"""Tests for expert parallelism (MoE) and pipeline parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from tensor2robot_tpu.layers.moe import MixtureOfExperts
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import pipeline_parallel as pp
from tensor2robot_tpu.parallel import train_step as ts


class TestMoE:

  def _moe(self, top_k=1):
    module = MixtureOfExperts(num_experts=4, hidden_size=8,
                              output_size=6, top_k=top_k)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 5))
    variables = module.init(jax.random.PRNGKey(1), x)
    return module, variables, x

  def test_shapes_and_aux_loss(self):
    module, variables, x = self._moe()
    out, aux = module.apply(variables, x)
    assert out.shape == (16, 6)
    assert np.isfinite(float(aux))
    assert float(aux) >= 1.0 - 1e-3  # Switch aux lower bound at balance

  def test_top2_gates_mix_experts(self):
    module, variables, x = self._moe(top_k=2)
    out, _ = module.apply(variables, x)
    assert out.shape == (16, 6)

  def test_expert_parallel_sharding(self):
    """Expert params shard over the model axis; forward stays correct."""
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 1, 4))
    module, variables, x = self._moe()
    rules = ((r"experts_", ("model", None, None)), (r".*", None))

    def leaf_sharding(path, leaf):
      path_str = jax.tree_util.keystr(path)
      if "experts_" in path_str:
        return NamedSharding(mesh, PartitionSpec("model"))
      return NamedSharding(mesh, PartitionSpec())

    sharded_vars = jax.tree_util.tree_map_with_path(
        lambda p, l: jax.device_put(l, leaf_sharding(p, l)), variables)
    expected, _ = module.apply(variables, x)
    got, _ = jax.jit(lambda v, x: module.apply(v, x))(sharded_vars, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-5)

  def test_gradients_flow_to_all_router_and_experts(self):
    module, variables, x = self._moe()

    def loss(v):
      out, aux = module.apply(v, x)
      return (out ** 2).mean() + 0.01 * aux

    grads = jax.grad(loss)(variables)["params"]
    assert float(jnp.abs(grads["router"]["kernel"]).max()) > 0
    assert float(jnp.abs(grads["experts_w1"]).max()) > 0


class TestSparseDispatch:

  def test_matches_dense_when_capacity_ample(self):
    """With capacity >= N every token is kept, so sparse == dense."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 5))
    dense = MixtureOfExperts(num_experts=4, hidden_size=8, output_size=6,
                             dispatch="dense")
    sparse = MixtureOfExperts(num_experts=4, hidden_size=8, output_size=6,
                              dispatch="sparse", capacity_factor=16.0)
    variables = dense.init(jax.random.PRNGKey(1), x)
    out_d, _ = dense.apply(variables, x)
    out_s, _ = sparse.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               atol=1e-5)

  def test_top2_matches_dense_when_capacity_ample(self):
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 5))
    dense = MixtureOfExperts(num_experts=4, hidden_size=8, output_size=6,
                             top_k=2, dispatch="dense")
    sparse = MixtureOfExperts(num_experts=4, hidden_size=8, output_size=6,
                              top_k=2, dispatch="sparse",
                              capacity_factor=16.0)
    variables = dense.init(jax.random.PRNGKey(1), x)
    out_d, _ = dense.apply(variables, x)
    out_s, _ = sparse.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               atol=1e-5)

  def test_tight_capacity_drops_overflow_tokens(self):
    """With capacity 1 per expert, later same-expert tokens get zero
    output (Switch token dropping)."""
    module = MixtureOfExperts(num_experts=2, hidden_size=4, output_size=3,
                              dispatch="sparse", capacity_factor=1e-9)
    x = jnp.ones((6, 5))  # identical tokens -> all route to one expert
    variables = module.init(jax.random.PRNGKey(0), x)
    out, _ = module.apply(variables, x)
    out = np.asarray(out)
    # capacity = 1: exactly one token computed, the rest dropped to 0
    nonzero_rows = (np.abs(out).sum(-1) > 1e-9).sum()
    assert nonzero_rows == 1, out

  def test_sparse_flops_scale_with_capacity_not_tokens(self):
    """The expert matmuls see [E, C, F] inputs: C from capacity, not N."""
    module = MixtureOfExperts(num_experts=4, hidden_size=8, output_size=6,
                              dispatch="sparse", capacity_factor=1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 5))
    variables = module.init(jax.random.PRNGKey(1), x)
    jaxpr = jax.make_jaxpr(
        lambda v, x: module.apply(v, x))(variables, x)

    def shapes(jpr):
      for eqn in jpr.eqns:
        for out in eqn.outvars:
          if hasattr(out, "aval") and hasattr(out.aval, "shape"):
            yield tuple(out.aval.shape)
        for param in eqn.params.values():
          inner = getattr(param, "jaxpr", None)
          if inner is not None:
            yield from shapes(inner)

    all_shapes = set(shapes(jaxpr.jaxpr))
    # dispatch packs tokens into [E=4, C=16, F=5] expert inputs; the
    # dense path would instead materialize [4, 64, 8] hiddens.
    assert (4, 16, 5) in all_shapes, sorted(all_shapes)
    assert (4, 64, 8) not in all_shapes, sorted(all_shapes)

  def test_sparse_gradients_flow(self):
    module = MixtureOfExperts(num_experts=4, hidden_size=8, output_size=6,
                              dispatch="sparse")
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 5))
    variables = module.init(jax.random.PRNGKey(1), x)

    def loss(v):
      out, aux = module.apply(v, x)
      return (out ** 2).mean() + 0.01 * aux

    grads = jax.grad(loss)(variables)["params"]
    assert float(jnp.abs(grads["router"]["kernel"]).max()) > 0
    assert float(jnp.abs(grads["experts_w1"]).max()) > 0


class TestMoEAllToAll:
  """Explicit shard_map + lax.all_to_all token routing (dispatch='alltoall')."""

  def _pair(self, num_experts=8, top_k=2, mesh_shape=(8, 1, 1),
            capacity_factor=64.0, n=32):
    mesh = mesh_lib.create_mesh(mesh_shape=mesh_shape)
    kw = dict(num_experts=num_experts, hidden_size=8, output_size=6,
              top_k=top_k)
    dense = MixtureOfExperts(dispatch="dense", **kw)
    a2a = MixtureOfExperts(dispatch="alltoall", mesh=mesh, ep_axis="data",
                           capacity_factor=capacity_factor, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 5))
    variables = dense.init(jax.random.PRNGKey(1), x)  # same param tree
    return dense, a2a, variables, x

  @pytest.mark.parametrize("mesh_shape,num_experts",
                           [((8, 1, 1), 8), ((4, 1, 1), 8)])
  def test_matches_dense_when_nothing_drops(self, mesh_shape, num_experts):
    dense, a2a, variables, x = self._pair(num_experts=num_experts,
                                          mesh_shape=mesh_shape)
    out_d, aux_d = dense.apply(variables, x)
    out_a, aux_a = jax.jit(lambda v, x: a2a.apply(v, x))(variables, x)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_d),
                               atol=2e-5)
    np.testing.assert_allclose(float(aux_a), float(aux_d), atol=2e-5)

  def test_grads_match_dense_when_nothing_drops(self):
    dense, a2a, variables, x = self._pair()

    def loss(module):
      def f(v):
        out, aux = module.apply(v, x)
        return (out ** 2).mean() + 0.01 * aux
      return f

    g_d = jax.grad(loss(dense))(variables)["params"]
    g_a = jax.jit(jax.grad(loss(a2a)))(variables)["params"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5), g_a, g_d)

  def test_capacity_drops_are_per_source_shard(self):
    """Pin the router so every token routes to expert 0; with 1 slot per
    expert, alltoall keeps the FIRST token of each source shard while
    sparse (global capacity) keeps the first `capacity` tokens of the
    batch — the documented per-shard-vs-global drop delta."""
    mesh = mesh_lib.create_mesh(mesh_shape=(8, 1, 1))
    kw = dict(num_experts=8, hidden_size=8, output_size=6, top_k=1)
    # alltoall: capacity = ceil(1 * n_local / E * cf) = ceil(4/8*1) = 1
    # sparse:   capacity = ceil(1 * n / E * cf)       = ceil(32/8)  = 4
    a2a = MixtureOfExperts(dispatch="alltoall", mesh=mesh, ep_axis="data",
                           capacity_factor=1.0, **kw)
    sparse = MixtureOfExperts(dispatch="sparse", capacity_factor=1.0, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 5))
    variables = sparse.init(jax.random.PRNGKey(1), x)
    # Router logits = +10 for expert 0, 0 elsewhere, for every token.
    kernel = variables["params"]["router"]["kernel"]
    pinned = jnp.zeros_like(kernel)
    bias = jnp.zeros((8,)).at[0].set(10.0)
    variables = {"params": {**variables["params"],
                            "router": {"kernel": pinned, "bias": bias}}}
    out_a = np.asarray(jax.jit(
        lambda v, x: a2a.apply(v, x)[0])(variables, x))
    out_s = np.asarray(jax.jit(
        lambda v, x: sparse.apply(v, x)[0])(variables, x))
    kept_a = set(np.nonzero(np.abs(out_a).sum(-1) > 1e-9)[0].tolist())
    kept_s = set(np.nonzero(np.abs(out_s).sum(-1) > 1e-9)[0].tolist())
    # 32 tokens over 8 shards of 4: alltoall keeps token 0 of each shard.
    assert kept_a == {0, 4, 8, 12, 16, 20, 24, 28}, kept_a
    # sparse packs globally in batch order: first 4 tokens keep slots.
    assert kept_s == {0, 1, 2, 3}, kept_s

  def test_requires_mesh_and_divisibility(self):
    module = MixtureOfExperts(num_experts=8, dispatch="alltoall")
    x = jnp.zeros((8, 5))
    with pytest.raises(ValueError, match="mesh"):
      module.init(jax.random.PRNGKey(0), x)
    mesh = mesh_lib.create_mesh(mesh_shape=(8, 1, 1))
    bad_experts = MixtureOfExperts(num_experts=6, dispatch="alltoall",
                                   mesh=mesh)
    with pytest.raises(ValueError, match="divisible"):
      bad_experts.init(jax.random.PRNGKey(0), x)
    bad_tokens = MixtureOfExperts(num_experts=8, dispatch="alltoall",
                                  mesh=mesh)
    with pytest.raises(ValueError, match="divisible"):
      bad_tokens.init(jax.random.PRNGKey(0), jnp.zeros((12, 5)))

  def test_trains_through_step_factory_on_data_axis(self):
    """EP over the data axis: experts co-sharded with tokens, explicit
    all_to_all dispatch inside the jitted train step."""
    from tensor2robot_tpu.models import moe_model
    from tensor2robot_tpu import specs as specs_lib
    import optax

    mesh = mesh_lib.create_mesh(mesh_shape=(8, 1, 1))
    model = moe_model.MoERegressionModel(
        obs_size=8, action_size=3, num_experts=8, hidden_size=16,
        dispatch="alltoall", capacity_factor=2.0, device_type="cpu",
        optimizer_fn=lambda: optax.adam(3e-3))
    model.set_mesh(mesh)
    features = specs_lib.make_random_numpy(
        model.get_feature_specification("train"), batch_size=64, seed=0)
    labels = specs_lib.make_random_numpy(
        model.get_label_specification("train"), batch_size=64, seed=1)
    rules = moe_model.expert_parallel_rules(axis="data")
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=mesh, rules=rules)
    expert_specs = [
        l.sharding.spec for p, l in
        jax.tree_util.tree_leaves_with_path(state.params)
        if "experts_w" in jax.tree_util.keystr(p)]
    assert expert_specs and all(
        s == PartitionSpec("data", None, None) for s in expert_specs)
    step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
    f = mesh_lib.put_host_batch(mesh, features)
    l = mesh_lib.put_host_batch(mesh, labels)
    first = None
    for _ in range(30):
      state, metrics = step(state, f, l)
      jax.block_until_ready(metrics)  # see conftest.py: one step in flight
      first = first if first is not None else float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))


class TestExpertParallelTrainStep:
  """EP as a *training capability*: MoERegressionModel through the
  generic step factory on a mesh, expert params sharded over 'model'."""

  def test_trains_sharded_and_loss_decreases(self):
    from tensor2robot_tpu.models import moe_model
    from tensor2robot_tpu import specs as specs_lib

    import optax

    mesh = mesh_lib.create_mesh(mesh_shape=(2, 1, 4))
    model = moe_model.MoERegressionModel(
        obs_size=8, action_size=3, num_experts=4, hidden_size=16,
        dispatch="sparse", device_type="cpu",
        optimizer_fn=lambda: optax.adam(3e-3))
    features = specs_lib.make_random_numpy(
        model.get_feature_specification("train"), batch_size=32, seed=0)
    labels = specs_lib.make_random_numpy(
        model.get_label_specification("train"), batch_size=32, seed=1)
    rules = moe_model.expert_parallel_rules()
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=mesh, rules=rules)
    # the expert params really are sharded over the model axis
    expert_sharding = jax.tree_util.tree_map_with_path(
        lambda p, l: (jax.tree_util.keystr(p), l.sharding.spec),
        state.params)
    flat = jax.tree_util.tree_leaves(
        expert_sharding, is_leaf=lambda x: isinstance(x, tuple))
    specs = {k: v for k, v in
             [x for x in flat if isinstance(x, tuple)]}
    expert_specs = [v for k, v in specs.items() if "experts_w" in k]
    assert expert_specs and all(
        s == PartitionSpec("model", None, None) for s in expert_specs), specs
    step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
    f = mesh_lib.put_host_batch(mesh, features)
    l = mesh_lib.put_host_batch(mesh, labels)
    first = None
    for _ in range(30):
      state, metrics = step(state, f, l)
      jax.block_until_ready(metrics)  # see conftest.py: one step in flight
      first = first if first is not None else float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))
    assert "moe_aux_loss" in metrics


def _stage_fn(params, x):
  return jnp.tanh(x @ params["w"] + params["b"])


def _stages(num_stages, dim, seed=0):
  keys = jax.random.split(jax.random.PRNGKey(seed), num_stages)
  return [
      {"w": jax.random.normal(k, (dim, dim)) / np.sqrt(dim),
       "b": jnp.zeros(dim)} for k in keys]


class TestPipelineParallel:

  @pytest.fixture(scope="class")
  def pp_mesh(self):
    return mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))

  def test_matches_sequential(self, pp_mesh):
    dim, num_micro, mb = 6, 5, 3
    stages = _stages(4, dim)
    stacked = pp.stack_stage_params(stages)
    micro = jax.random.normal(jax.random.PRNGKey(2), (num_micro, mb, dim))
    out = pp.pipelined_apply(_stage_fn, stacked, micro, pp_mesh,
                             axis_name="pp")
    expected = micro
    for params in stages:
      expected = jax.vmap(lambda x, p=params: _stage_fn(p, x))(expected)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)

  def test_differentiable(self, pp_mesh):
    dim = 4
    stages = pp.stack_stage_params(_stages(4, dim))
    micro = jax.random.normal(jax.random.PRNGKey(3), (3, 2, dim))

    @jax.jit
    def loss(params):
      out = pp.pipelined_apply(_stage_fn, params, micro, pp_mesh, "pp")
      return (out ** 2).sum()

    grads = jax.grad(loss)(stages)
    assert np.isfinite(np.asarray(grads["w"])).all()
    assert float(jnp.abs(grads["w"]).max()) > 0

  def test_composes_with_data_parallel_batch_sharding(self, pp_mesh):
    """batch_axis keeps the microbatch dim sharded over 'data' instead of
    all-gathering it (PP x DP composition)."""
    dim, num_micro, mb = 6, 4, 4
    stages = _stages(4, dim)
    stacked = pp.stack_stage_params(stages)
    micro = jax.random.normal(jax.random.PRNGKey(2), (num_micro, mb, dim))
    out = pp.pipelined_apply(_stage_fn, stacked, micro, pp_mesh,
                             axis_name="pp", batch_axis="data")
    expected = micro
    for params in stages:
      expected = jax.vmap(lambda x, p=params: _stage_fn(p, x))(expected)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5)

  def test_pipelined_training_step(self, pp_mesh):
    """PP as a *training capability*: the pipelined train step fits a
    target and matches the gradients of the sequential equivalent."""
    import optax

    dim, num_micro, mb = 6, 4, 3
    stages = _stages(4, dim)
    stacked = pp.stack_stage_params(stages)
    optimizer = optax.adam(1e-2)
    x = jax.random.normal(jax.random.PRNGKey(0), (num_micro, mb, dim))
    y = jax.random.normal(jax.random.PRNGKey(1), (num_micro, mb, dim))

    def loss_fn(outputs, targets):
      return ((outputs - targets) ** 2).mean()

    step = pp.make_pipelined_train_step(_stage_fn, loss_fn, optimizer,
                                        pp_mesh, axis_name="pp")
    params = pp.shard_pipeline_tree(stacked, pp_mesh, "pp")
    opt_state = pp.shard_pipeline_tree(optimizer.init(stacked), pp_mesh,
                                       "pp")
    # gradient check vs sequential (non-pipelined) execution
    def sequential_loss(p):
      out = x
      for i in range(4):
        stage_p = jax.tree_util.tree_map(lambda l, i=i: l[i], p)
        out = jax.vmap(lambda a, sp=stage_p: _stage_fn(sp, a))(out)
      return loss_fn(out, y)

    g_seq = jax.grad(sequential_loss)(stacked)
    g_pipe = jax.grad(lambda p: loss_fn(
        pp.pipelined_apply(_stage_fn, p, x, pp_mesh, "pp"), y))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq)):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    first = None
    for _ in range(60):
      params, opt_state, loss = step(params, opt_state, x, y)
      jax.block_until_ready(loss)  # see conftest.py: one step in flight
      first = first if first is not None else float(loss)
    assert float(loss) < first * 0.5, (first, float(loss))
    # params stayed sharded over the pp axis
    assert params["w"].sharding.spec == PartitionSpec("pp")


class TestPipelinedModelTrainStep:
  """PP as a T2RModel training capability (models/pipelined_model.py):
  the GPipe trunk runs through the generic step factory and
  train_eval_model, stage params sharded over 'pp'."""

  def _model(self, **kwargs):
    import optax

    from tensor2robot_tpu.models import pipelined_model

    kwargs.setdefault("obs_size", 8)
    kwargs.setdefault("action_size", 3)
    kwargs.setdefault("hidden_size", 16)
    kwargs.setdefault("num_stages", 4)
    kwargs.setdefault("num_microbatches", 4)
    kwargs.setdefault("device_type", "cpu")
    kwargs.setdefault("optimizer_fn", lambda: optax.adam(3e-3))
    return pipelined_model.PipelinedRegressionModel(**kwargs)

  def _batch(self, model, batch_size=16):
    from tensor2robot_tpu import specs as specs_lib

    features = specs_lib.make_random_numpy(
        model.get_feature_specification("train"), batch_size=batch_size,
        seed=0)
    labels = specs_lib.make_random_numpy(
        model.get_label_specification("train"), batch_size=batch_size,
        seed=1)
    return features, labels

  def test_pipelined_step_matches_sequential_step(self):
    """Same init, one train step: the pipelined schedule on a pp mesh
    produces the same loss and updated params as the sequential trunk
    (GPipe is a schedule, not a different function)."""
    from tensor2robot_tpu.models import pipelined_model

    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    results = {}
    for name, use_mesh in (("seq", False), ("pp", True)):
      model = self._model()
      features, labels = self._batch(model)
      if use_mesh:
        model.set_mesh(mesh)
        state, shardings = ts.create_train_state(
            model, jax.random.PRNGKey(0), features, mesh=mesh,
            rules=pipelined_model.pipeline_parallel_rules())
        step = ts.make_train_step(model, mesh=mesh, shardings=shardings,
                                  donate=False)
        f = mesh_lib.put_host_batch(mesh, features)
        l = mesh_lib.put_host_batch(mesh, labels)
      else:
        state, _ = ts.create_train_state(model, jax.random.PRNGKey(0),
                                         features)
        step = ts.make_train_step(model, donate=False)
        f, l = features, labels
      new_state, metrics = step(state, f, l)
      results[name] = (float(metrics["loss"]),
                       jax.device_get(new_state.params))
    assert results["pp"][0] == pytest.approx(results["seq"][0], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(results["pp"][1]),
                    jax.tree_util.tree_leaves(results["seq"][1])):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

  def test_stage_params_sharded_and_loss_decreases(self):
    from tensor2robot_tpu.models import pipelined_model

    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    model = self._model()
    model.set_mesh(mesh)
    features, labels = self._batch(model, batch_size=32)
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=mesh,
        rules=pipelined_model.pipeline_parallel_rules())
    w1 = state.params["stages_w1"]
    assert w1.sharding.spec == PartitionSpec("pp", None, None), w1.sharding
    step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
    f = mesh_lib.put_host_batch(mesh, features)
    l = mesh_lib.put_host_batch(mesh, labels)
    first = None
    for _ in range(40):
      state, metrics = step(state, f, l)
      jax.block_until_ready(metrics)  # see conftest.py: one step in flight
      first = first if first is not None else float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))

  def test_set_mesh_rejects_stage_mismatch(self):
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    model = self._model(num_stages=3)
    with pytest.raises(ValueError, match="must match"):
      model.set_mesh(mesh)

  def test_indivisible_microbatch_raises(self):
    model = self._model(num_microbatches=5)
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    model.set_mesh(mesh)
    features, _ = self._batch(model, batch_size=16)  # 16 % 5 != 0
    with pytest.raises(ValueError, match="microbatches"):
      ts.create_train_state(model, jax.random.PRNGKey(0), features,
                            mesh=mesh)


class TestHeterogeneousPipeline:
  """Per-stage different functions, param pytrees, and activation shapes
  (round-2 scoping excluded these; pipelined_apply_heterogeneous)."""

  @pytest.fixture(scope="class")
  def pp_mesh(self):
    return mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))

  def _setup(self):
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    p0 = {"w": jax.random.normal(key[0], (12, 20)) * 0.1,
          "b": jnp.zeros(20)}
    p1 = {"w": jax.random.normal(key[1], (20, 7)) * 0.1}
    p2 = {"w1": jax.random.normal(key[2], (7, 9)) * 0.1,
          "w2": jax.random.normal(key[3], (9, 5)) * 0.1}
    p3 = {"w": jax.random.normal(key[4], (5, 3)) * 0.1, "b": jnp.ones(3)}

    def s0(p, x):
      return jnp.tanh(x[:, :12] @ p["w"] + p["b"])

    def s1(p, x):
      return jax.nn.relu(x[:, :20] @ p["w"])

    def s2(p, x):
      return jnp.tanh(x[:, :7] @ p["w1"]) @ p["w2"]

    def s3(p, x):
      return x[:, :5] @ p["w"] + p["b"]

    fns = [s0, s1, s2, s3]
    stacked, unravels, sizes = pp.ravel_stage_stack([p0, p1, p2, p3])
    a_max = 20
    x = jax.random.normal(key[5], (4, 2, 12))
    micro = jnp.pad(x, ((0, 0), (0, 0), (0, a_max - 12)))
    return fns, unravels, sizes, stacked, micro

  def test_param_stack_pads_to_widest_stage(self):
    _, _, sizes, stacked, _ = self._setup()
    assert stacked.shape == (4, max(sizes))
    assert sizes == [260, 140, 108, 18]

  def test_matches_sequential(self, pp_mesh):
    fns, unravels, sizes, stacked, micro = self._setup()
    seq = pp.sequential_apply_heterogeneous(fns, unravels, sizes, stacked,
                                            micro)
    out = pp.pipelined_apply_heterogeneous(fns, unravels, sizes, stacked,
                                           micro, pp_mesh,
                                           batch_axis="data")
    np.testing.assert_allclose(np.asarray(seq), np.asarray(out), rtol=1e-6)

  def test_gradients_match_sequential(self, pp_mesh):
    fns, unravels, sizes, stacked, micro = self._setup()

    def loss_seq(sp):
      out = pp.sequential_apply_heterogeneous(fns, unravels, sizes, sp,
                                              micro)
      return jnp.mean(out[..., :3] ** 2)

    def loss_pp(sp):
      out = pp.pipelined_apply_heterogeneous(fns, unravels, sizes, sp,
                                             micro, pp_mesh,
                                             batch_axis="data")
      return jnp.mean(out[..., :3] ** 2)

    g_seq = jax.grad(loss_seq)(stacked)
    g_pp = jax.jit(jax.grad(loss_pp))(stacked)
    np.testing.assert_allclose(np.asarray(g_seq), np.asarray(g_pp),
                               rtol=1e-5, atol=1e-7)

  def test_stage_count_mesh_mismatch_raises(self, pp_mesh):
    fns, unravels, sizes, stacked, micro = self._setup()
    with pytest.raises(ValueError, match="stage functions"):
      pp.pipelined_apply_heterogeneous(fns[:3], unravels[:3], sizes[:3],
                                       stacked[:3], micro, pp_mesh)


class TestBCZPipelined:
  """The real-family PP integration: BCZ's conv trunk as heterogeneous
  GPipe stages (research/bcz/configs/train_bcz_pp.gin)."""

  @pytest.fixture(scope="class")
  def pp_mesh(self):
    return mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))

  def _model(self, mesh):
    from tensor2robot_tpu.research.bcz import models as bcz_models

    model = bcz_models.BCZModel(
        image_size=32, network="pipelined_berkeley", num_waypoints=3,
        condition_mode="language", condition_size=8, device_type="cpu",
        pipeline_microbatches=4)
    model.set_mesh(mesh)
    return model

  def _batch(self, model, batch_size=8):
    from tensor2robot_tpu import modes, specs as specs_lib

    features = specs_lib.make_random_numpy(
        model.get_feature_specification(modes.TRAIN),
        batch_size=batch_size, seed=0)
    labels = specs_lib.make_random_numpy(
        model.get_label_specification(modes.TRAIN),
        batch_size=batch_size, seed=1)
    return features, labels

  def test_forward_and_grads_match_sequential(self, pp_mesh):
    """Same params through the pipelined and sequential schedules give
    identical outputs AND parameter gradients — GPipe is an execution
    schedule, not a different function."""
    from tensor2robot_tpu import modes

    model_pp = self._model(pp_mesh)
    model_seq = self._model(None)
    features, labels = self._batch(model_pp)
    variables = model_seq.module.init(jax.random.PRNGKey(0), features,
                                      train=False)

    out_seq = model_seq.module.apply(variables, features, train=False)
    with pp_mesh:
      out_pp = model_pp.module.apply(variables, features, train=False)
    for key in out_seq:
      np.testing.assert_allclose(np.asarray(out_seq[key]),
                                 np.asarray(out_pp[key]),
                                 rtol=2e-5, atol=1e-5)

    def loss(params, model):
      out = model.module.apply({"params": params}, features, train=False)
      value, _ = model.model_train_fn(features, labels, out, modes.TRAIN)
      return value

    g_seq = jax.grad(lambda p: loss(p, model_seq))(variables["params"])
    with pp_mesh:
      g_pp = jax.jit(jax.grad(lambda p: loss(p, model_pp)))(
          variables["params"])
    flat_pp = dict(jax.tree_util.tree_leaves_with_path(g_pp))
    for path, leaf in jax.tree_util.tree_leaves_with_path(g_seq):
      np.testing.assert_allclose(np.asarray(leaf),
                                 np.asarray(flat_pp[path]),
                                 rtol=1e-4, atol=1e-5,
                                 err_msg=str(path))

  def test_trains_with_stage_params_sharded(self, pp_mesh):
    """Through the step factory: pp_stages lands sharded over 'pp' and
    the loss decreases."""
    from tensor2robot_tpu.models import pipelined_model

    model = self._model(pp_mesh)
    features, labels = self._batch(model, batch_size=16)
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=pp_mesh,
        rules=pipelined_model.pipeline_parallel_rules())
    stages = state.params["_BCZNetwork_0"]["tower"]["pp_stages"] \
        if "_BCZNetwork_0" in state.params else None
    if stages is None:  # param path depends on flax module nesting
      flat = {"/".join(str(getattr(p, "key", p)) for p in path): leaf
              for path, leaf in
              jax.tree_util.tree_leaves_with_path(state.params)}
      stages = next(v for k, v in flat.items() if "pp_stages" in k)
    assert stages.sharding.spec == PartitionSpec("pp", None), \
        stages.sharding
    step = ts.make_train_step(model, mesh=pp_mesh, shardings=shardings)
    f = mesh_lib.put_host_batch(pp_mesh, features)
    l = mesh_lib.put_host_batch(pp_mesh, labels)
    first = None
    for _ in range(15):
      state, metrics = step(state, f, l)
      jax.block_until_ready(metrics)  # see conftest.py: one step in flight
      first = first if first is not None else float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))

  def test_set_mesh_rejects_stage_mismatch(self):
    from tensor2robot_tpu.research.bcz import models as bcz_models

    mesh = mesh_lib.create_mesh(mesh_shape=(1, 8, 1),
                                axis_names=("data", "pp", "model"))
    model = bcz_models.BCZModel(
        image_size=32, network="pipelined_berkeley", device_type="cpu")
    with pytest.raises(ValueError, match="must match"):
      model.set_mesh(mesh)


class TestGrasp2VecPipelined:
  """Second research family on heterogeneous PP: Grasp2Vec's scene and
  goal conv towers as GPipe stages (configs/train_grasp2vec_pp.gin)."""

  @pytest.fixture(scope="class")
  def pp_mesh(self):
    return mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))

  def _model(self, mesh):
    from tensor2robot_tpu.research.grasp2vec import models as g2v_models

    model = g2v_models.Grasp2VecModel(
        image_size=32, tower="pipelined_conv",
        filters=(16, 32, 32, 32), device_type="cpu",
        pipeline_microbatches=4)
    model.set_mesh(mesh)
    return model

  def _batch(self, model, batch_size=8):
    from tensor2robot_tpu import modes, specs as specs_lib

    features = specs_lib.make_random_numpy(
        model.get_feature_specification(modes.TRAIN),
        batch_size=batch_size, seed=0)
    labels = specs_lib.make_random_numpy(
        model.get_label_specification(modes.TRAIN),
        batch_size=batch_size, seed=1)
    return features, labels

  def test_forward_and_grads_match_sequential(self, pp_mesh):
    """Same params through the pipelined and sequential schedules give
    identical embeddings AND parameter gradients for BOTH towers."""
    from tensor2robot_tpu import modes

    model_pp = self._model(pp_mesh)
    model_seq = self._model(None)
    features, labels = self._batch(model_pp)
    variables = model_seq.module.init(jax.random.PRNGKey(0), features,
                                      train=False)

    out_seq = model_seq.module.apply(variables, features, train=False)
    with pp_mesh:
      out_pp = model_pp.module.apply(variables, features, train=False)
    for key in ("pregrasp_embedding", "postgrasp_embedding",
                "goal_embedding", "arithmetic_embedding", "heatmap"):
      np.testing.assert_allclose(np.asarray(out_seq[key]),
                                 np.asarray(out_pp[key]),
                                 rtol=2e-5, atol=1e-5, err_msg=key)

    def loss(params, model):
      out = model.module.apply({"params": params}, features, train=False)
      value, _ = model.model_train_fn(features, labels, out, modes.TRAIN)
      return value

    g_seq = jax.grad(lambda p: loss(p, model_seq))(variables["params"])
    with pp_mesh:
      g_pp = jax.jit(jax.grad(lambda p: loss(p, model_pp)))(
          variables["params"])
    flat_pp = dict(jax.tree_util.tree_leaves_with_path(g_pp))
    for path, leaf in jax.tree_util.tree_leaves_with_path(g_seq):
      np.testing.assert_allclose(np.asarray(leaf),
                                 np.asarray(flat_pp[path]),
                                 rtol=1e-4, atol=1e-5,
                                 err_msg=str(path))

  def test_trains_with_stage_params_sharded(self, pp_mesh):
    """Through the step factory: BOTH towers' pp_stages leaves land
    sharded over 'pp' and the npairs loss decreases."""
    from tensor2robot_tpu.models import pipelined_model

    model = self._model(pp_mesh)
    features, labels = self._batch(model, batch_size=16)
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=pp_mesh,
        rules=pipelined_model.pipeline_parallel_rules())
    flat = {"/".join(str(getattr(p, "key", p)) for p in path): leaf
            for path, leaf in
            jax.tree_util.tree_leaves_with_path(state.params)}
    stage_leaves = {k: v for k, v in flat.items() if "pp_stages" in k}
    assert len(stage_leaves) == 2, list(flat)  # scene + goal towers
    for key, leaf in stage_leaves.items():
      assert leaf.sharding.spec == PartitionSpec("pp", None), (key,
                                                               leaf.sharding)
    step = ts.make_train_step(model, mesh=pp_mesh, shardings=shardings)
    f = mesh_lib.put_host_batch(pp_mesh, features)
    l = mesh_lib.put_host_batch(pp_mesh, labels)
    first = None
    for _ in range(15):
      state, metrics = step(state, f, l)
      jax.block_until_ready(metrics)  # see conftest.py: one step in flight
      first = first if first is not None else float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))

  def test_set_mesh_rejects_stage_mismatch(self):
    from tensor2robot_tpu.research.grasp2vec import models as g2v_models

    mesh = mesh_lib.create_mesh(mesh_shape=(1, 8, 1),
                                axis_names=("data", "pp", "model"))
    model = g2v_models.Grasp2VecModel(
        image_size=32, tower="pipelined_conv", device_type="cpu")
    with pytest.raises(ValueError, match="must match"):
      model.set_mesh(mesh)


class TestScheduleAccounting:
  """Static idle-tick accounting: the observable the 1F1B upgrade is
  gated on (pure Python — the poisoned trap below imports it with no
  usable backend)."""

  def test_gpipe_formula(self):
    acc = pp.schedule_accounting(4, 8, 1)
    assert acc["schedule"] == "gpipe"
    assert acc["total_ticks"] == 8 + 4 - 1
    assert acc["busy_ticks_per_rank"] == 8
    assert acc["bubble_fraction"] == pytest.approx(3 / 11)
    assert acc["padded_microbatches"] == 0

  def test_interleaved_strictly_beats_gpipe_at_s4_m8(self):
    """The ISSUE acceptance pin: bubble fraction strictly below GPipe's
    for v>1 at S=4, M=8 — and exactly the (S-1)/(v*M + S-1) closed
    form when S | M."""
    gpipe = pp.schedule_accounting(4, 8, 1)
    onefonb = pp.schedule_accounting(4, 8, 2)
    assert onefonb["total_ticks"] == 2 * 8 + 4 - 1  # v*M + S - 1
    assert onefonb["bubble_fraction"] == pytest.approx(3 / 19)
    assert onefonb["bubble_fraction"] < gpipe["bubble_fraction"]
    # more virtual stages keep shrinking the bubble
    v4 = pp.schedule_accounting(4, 8, 4)
    assert v4["bubble_fraction"] < onefonb["bubble_fraction"]

  def test_ragged_group_pays_padding(self):
    acc = pp.schedule_accounting(4, 5, 2)
    assert acc["padded_microbatches"] == 3
    # padded slots are idle: busy counts only REAL microbatch work
    assert acc["busy_ticks_per_rank"] == 5 * 2
    assert acc["total_ticks"] == 2 * 4 * 2 + 4 - 1

  def test_validation(self):
    with pytest.raises(ValueError, match="num_stages"):
      pp.schedule_accounting(0, 8, 1)
    with pytest.raises(ValueError, match="num_stages"):
      pp.schedule_accounting(4, 0, 1)

  def test_interleave_order_places_loop_major_chunks(self):
    # position r*v + j holds layer j*S + r
    order = pp.interleave_order(4, 2)
    assert order.tolist() == [0, 4, 1, 5, 2, 6, 3, 7]
    stacked = jnp.arange(8.0)
    inter = pp.interleave_stage_stack(stacked, 4, 2)
    assert inter.tolist() == [0.0, 4.0, 1.0, 5.0, 2.0, 6.0, 3.0, 7.0]


class TestInterleavedPipeline:
  """1F1B equivalence: loss AND gradient parity vs the sequential
  schedule across (S, M, v, batch_axis) combos on the 8-device mesh."""

  @pytest.fixture(scope="class")
  def pp_mesh(self):
    return mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))

  def _sequential(self, layers, micro):
    out = micro
    for params in layers:
      out = jax.vmap(lambda x, p=params: _stage_fn(p, x))(out)
    return out

  @pytest.mark.parametrize("num_micro,v,batch_axis",
                           [(8, 2, None), (5, 2, None), (3, 2, None),
                            (8, 2, "data"), (4, 1, "data"), (8, 4, None)])
  def test_forward_matches_sequential(self, pp_mesh, num_micro, v,
                                      batch_axis):
    dim, mb = 6, 4
    layers = _stages(4 * v, dim)
    stacked = pp.stack_stage_params(layers)
    micro = jax.random.normal(jax.random.PRNGKey(2), (num_micro, mb, dim))
    out = pp.pipelined_apply(_stage_fn, stacked, micro, pp_mesh,
                             axis_name="pp", batch_axis=batch_axis,
                             num_virtual_stages=v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(self._sequential(layers, micro)),
                               atol=1e-5)

  def test_forward_interleaved_layout_matches(self, pp_mesh):
    """Pre-permuted stacks (`params_layout='interleaved'`) are the same
    function — the production layout that keeps the permute gather off
    the per-step program."""
    dim, num_micro, v = 6, 8, 2
    layers = _stages(4 * v, dim)
    stacked = pp.interleave_stage_stack(pp.stack_stage_params(layers), 4, v)
    micro = jax.random.normal(jax.random.PRNGKey(2), (num_micro, 4, dim))
    out = pp.pipelined_apply(_stage_fn, stacked, micro, pp_mesh,
                             axis_name="pp", num_virtual_stages=v,
                             params_layout="interleaved")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(self._sequential(layers, micro)),
                               atol=1e-5)

  @pytest.mark.parametrize("batch_axis", [None, "data"])
  def test_gradients_match_sequential(self, pp_mesh, batch_axis):
    dim, num_micro, v = 6, 8, 2
    layers = _stages(4 * v, dim)
    stacked = pp.stack_stage_params(layers)
    micro = jax.random.normal(jax.random.PRNGKey(3), (num_micro, 4, dim))

    def loss_pp(p):
      out = pp.pipelined_apply(_stage_fn, p, micro, pp_mesh, "pp",
                               batch_axis=batch_axis,
                               num_virtual_stages=v)
      return (out ** 2).mean()

    def loss_seq(p):
      out = micro
      for i in range(4 * v):
        sp = jax.tree_util.tree_map(lambda l, i=i: l[i], p)
        out = jax.vmap(lambda a, sp=sp: _stage_fn(sp, a))(out)
      return (out ** 2).mean()

    g_pp = jax.jit(jax.grad(loss_pp))(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                    jax.tree_util.tree_leaves(g_seq)):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                 rtol=1e-4, atol=1e-6)

  def test_heterogeneous_interleaved_matches_sequential(self, pp_mesh):
    """The lax.switch flat-buffer path on the SAME 1F1B skeleton: 8
    different stages (2 chunks per rank), forward AND gradients vs the
    `sequential_apply_heterogeneous` oracle, composed with batch DP."""
    key = jax.random.split(jax.random.PRNGKey(0), 9)
    dims = [10, 12, 8, 9, 7, 11, 6, 5, 4]
    params, fns = [], []
    for i in range(8):
      params.append({"w": jax.random.normal(key[i],
                                            (dims[i], dims[i + 1])) * 0.2})

      def fn(p, x, d_in=dims[i]):
        return jnp.tanh(x[:, :d_in] @ p["w"])

      fns.append(fn)
    stacked, unravels, sizes, = pp.ravel_stage_stack(params)
    a_max = max(dims)
    micro = jnp.pad(
        jax.random.normal(key[8], (8, 2, dims[0])),
        ((0, 0), (0, 0), (0, a_max - dims[0])))

    seq = pp.sequential_apply_heterogeneous(fns, unravels, sizes, stacked,
                                            micro)
    out = pp.pipelined_apply_heterogeneous(
        fns, unravels, sizes, stacked, micro, pp_mesh,
        batch_axis="data", num_virtual_stages=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                               rtol=1e-5, atol=1e-6)

    def loss_seq(sp):
      o = pp.sequential_apply_heterogeneous(fns, unravels, sizes, sp,
                                            micro)
      return jnp.mean(o[..., :dims[-1]] ** 2)

    def loss_pp(sp):
      o = pp.pipelined_apply_heterogeneous(
          fns, unravels, sizes, sp, micro, pp_mesh,
          batch_axis="data", num_virtual_stages=2)
      return jnp.mean(o[..., :dims[-1]] ** 2)

    g_seq = jax.grad(loss_seq)(stacked)
    g_pp = jax.jit(jax.grad(loss_pp))(stacked)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                               rtol=1e-4, atol=1e-7)

  def test_heterogeneous_stage_count_mismatch_raises(self, pp_mesh):
    fns, unravels, sizes, stacked, micro = (
        TestHeterogeneousPipeline()._setup())
    with pytest.raises(ValueError, match="stage functions"):
      pp.pipelined_apply_heterogeneous(fns, unravels, sizes, stacked,
                                       micro, pp_mesh,
                                       num_virtual_stages=2)

  def test_homogeneous_stage_count_mismatch_raises(self, pp_mesh):
    stacked = pp.stack_stage_params(_stages(6, 4))
    micro = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 4))
    with pytest.raises(ValueError, match="leading dim"):
      pp.pipelined_apply(_stage_fn, stacked, micro, pp_mesh, "pp",
                         num_virtual_stages=2)

  def test_num_micro_validation_and_degenerate_warning(self, pp_mesh):
    from tensor2robot_tpu.obs import metrics as obs_metrics

    stacked = pp.stack_stage_params(_stages(4, 4))
    with pytest.raises(ValueError, match="num_micro"):
      pp.pipelined_apply(_stage_fn, stacked,
                         jnp.zeros((0, 2, 4)), pp_mesh, "pp")
    with obs_metrics.isolated():
      micro = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 4))
      pp.pipelined_apply(_stage_fn, stacked, micro, pp_mesh, "pp")
      snap = obs_metrics.snapshot(prefix="pp/")
    # M=2 < S=4: >50% bubble — counted via the telemetry registry.
    assert snap["counter/pp/degenerate_microbatching"] == 1.0
    assert snap["gauge/pp/bubble_fraction"] == pytest.approx(3 / 5)


class TestInterleavedTrainStep:
  """1F1B as a *training capability*: donated optimizer flow, the
  analyze_jit audit seam, schedule telemetry, and a zero-recompile pin."""

  @pytest.fixture(scope="class")
  def pp_mesh(self):
    return mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))

  def _setup(self, v=2, dim=6, num_micro=8, mb=3):
    import optax

    layers = _stages(4 * v, dim)
    stacked = pp.stack_stage_params(layers)
    optimizer = optax.adam(1e-2)
    x = jax.random.normal(jax.random.PRNGKey(0), (num_micro, mb, dim))
    y = jax.random.normal(jax.random.PRNGKey(1), (num_micro, mb, dim))

    def loss_fn(outputs, targets):
      return ((outputs - targets) ** 2).mean()

    return layers, stacked, optimizer, x, y, loss_fn

  def test_1f1b_step_gradients_match_sequential_and_loss_decreases(
      self, pp_mesh):
    from tensor2robot_tpu.obs import metrics as obs_metrics

    v = 2
    layers, stacked, optimizer, x, y, loss_fn = self._setup(v=v)

    def sequential_loss(p):
      out = x
      for i in range(4 * v):
        stage_p = jax.tree_util.tree_map(lambda l, i=i: l[i], p)
        out = jax.vmap(lambda a, sp=stage_p: _stage_fn(sp, a))(out)
      return loss_fn(out, y)

    g_seq = jax.grad(sequential_loss)(stacked)
    g_pipe = jax.grad(lambda p: loss_fn(
        pp.pipelined_apply(_stage_fn, p, x, pp_mesh, "pp",
                           num_virtual_stages=v), y))(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq)):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    with obs_metrics.isolated():
      step = pp.make_pipelined_train_step(
          _stage_fn, loss_fn, optimizer, pp_mesh, axis_name="pp",
          num_virtual_stages=v, audit_name="test/pp_1f1b_train_step")
      params = pp.shard_pipeline_tree(stacked, pp_mesh, "pp", v)
      opt_state = pp.shard_pipeline_tree(optimizer.init(stacked), pp_mesh,
                                         "pp", v)
      first = None
      for _ in range(80):
        params, opt_state, loss = step(params, opt_state, x, y)
        jax.block_until_ready(loss)  # see conftest.py: one step in flight
        first = first if first is not None else float(loss)
      snap = obs_metrics.snapshot(prefix="pp/")
    assert float(loss) < first * 0.5, (first, float(loss))
    # params stayed sharded over the pp axis
    assert params["w"].sharding.spec == PartitionSpec("pp")
    # the audit seam delivered: per-stage donation bytes + schedule
    # telemetry from the SAME build (the pp-schedule-unaudited contract)
    assert step.record is not None
    assert step.record["donated_bytes"] > 0
    assert snap["gauge/pp/bubble_fraction"] == pytest.approx(3 / 19)
    assert snap["gauge/pp/num_virtual_stages"] == v

  def test_zero_recompile_across_step_counts(self, pp_mesh):
    """The jitted 1F1B step compiles ONCE whatever the invocation
    count — the scan's tick structure is static, so step count cannot
    leak into trace shape."""
    _, stacked, optimizer, x, y, loss_fn = self._setup()
    step = pp.make_pipelined_train_step(  # graftlint: disable=pp-schedule-unaudited
        _stage_fn, loss_fn, optimizer, pp_mesh, axis_name="pp",
        num_virtual_stages=2)
    params = pp.shard_pipeline_tree(stacked, pp_mesh, "pp", 2)
    opt_state = pp.shard_pipeline_tree(optimizer.init(stacked), pp_mesh,
                                       "pp", 2)
    for n_steps in (1, 3, 7):
      for _ in range(n_steps):
        params, opt_state, _ = step(params, opt_state, x, y)
        jax.block_until_ready(params)  # see conftest.py: one step in flight
    assert step._cache_size() == 1

  def test_donation_declared_on_state(self, pp_mesh):
    """donate=True really donates (params, opt_state) and nothing else:
    the audited record's donated bytes equal the state pytree's bytes."""
    from tensor2robot_tpu.obs import xray as xray_lib

    _, stacked, optimizer, x, y, loss_fn = self._setup()
    step = pp.make_pipelined_train_step(
        _stage_fn, loss_fn, optimizer, pp_mesh, axis_name="pp",
        num_virtual_stages=2, audit_name="test/pp_donation_audit")
    params = pp.shard_pipeline_tree(stacked, pp_mesh, "pp", 2)
    opt_state = pp.shard_pipeline_tree(optimizer.init(stacked), pp_mesh,
                                       "pp", 2)
    params, opt_state, _ = step(params, opt_state, x, y)
    expected = (xray_lib.pytree_bytes(params)
                + xray_lib.pytree_bytes(opt_state))
    assert step.record["donated_bytes"] == expected


class TestPPScheduleLintRule:
  """graftlint `pp-schedule-unaudited` (analysis/pp_check.py): building
  a pipelined train step outside the analyze_jit audit path is a static
  finding, like thread_check/cache_check siblings."""

  def _findings(self, source):
    from tensor2robot_tpu.analysis import pp_check
    from tensor2robot_tpu.analysis.findings import (filter_findings,
                                                    load_suppressions)

    return filter_findings(pp_check.check_python_source("x.py", source),
                           load_suppressions(source))

  def test_flags_unaudited_call(self):
    findings = self._findings(
        "step = pp.make_pipelined_train_step(fn, loss, opt, mesh)\n")
    assert [f.rule for f in findings] == ["pp-schedule-unaudited"]
    assert "audit_name" in findings[0].message

  def test_flags_explicit_none(self):
    findings = self._findings(
        "step = make_pipelined_train_step(fn, loss, opt, mesh,\n"
        "                                 audit_name=None)\n")
    assert len(findings) == 1

  def test_audited_and_splat_clean(self):
    assert not self._findings(
        "s = make_pipelined_train_step(fn, loss, opt, mesh,\n"
        "                              audit_name='run/pp_step')\n")
    assert not self._findings(
        "s = make_pipelined_train_step(fn, loss, opt, mesh, **kw)\n")

  def test_suppression(self):
    assert not self._findings(
        "s = make_pipelined_train_step(fn, loss, opt, mesh)"
        "  # graftlint: disable=pp-schedule-unaudited\n")

  def test_wired_into_lint_run(self, tmp_path):
    from tensor2robot_tpu.analysis import lint

    bad = tmp_path / "bad_pp.py"
    bad.write_text("s = make_pipelined_train_step(f, l, o, m)\n")
    findings = lint.run([str(bad)])
    assert any(f.rule == "pp-schedule-unaudited" for f in findings)
    from tensor2robot_tpu.analysis import engine
    assert "pp-schedule-unaudited" in engine.catalog_text()


class TestPPBenchGating:
  """runs.jsonl vocabulary for the pipeline bench: key_metrics folds the
  two schedule metrics and diff_records gates them direction-aware."""

  def _rec(self, ratio, bubble):
    from tensor2robot_tpu.obs import runlog

    return runlog.make_record(
        "bench", platform="cpu", device_kind="host-pp-smoke",
        bench={"metric": "qtopt_pp_bubble_frac_cpu_smoke",
               "value": bubble, "unit": "bubble_fraction",
               "onefonb_vs_gpipe": ratio,
               "pp_bubble_fraction": bubble})

  def test_key_metrics_and_thresholds(self):
    from tensor2robot_tpu.obs import runlog

    metrics = runlog.key_metrics(self._rec(1.02, 3 / 19))
    assert metrics["onefonb_vs_gpipe"] == pytest.approx(1.02)
    assert metrics["pp_bubble_fraction"] == pytest.approx(3 / 19)
    # the bubble-fraction value must NOT masquerade as a throughput
    assert "examples_per_sec" not in metrics
    assert runlog.DEFAULT_THRESHOLDS["onefonb_vs_gpipe"] == ("down", 0.15)
    assert runlog.DEFAULT_THRESHOLDS["pp_bubble_fraction"][0] == "up"

  def test_ratio_collapse_and_bubble_growth_flagged(self):
    from tensor2robot_tpu.obs import runlog

    deltas = {d["metric"]: d
              for d in runlog.diff_records(self._rec(1.0, 3 / 19),
                                           self._rec(0.7, 3 / 19))}
    assert deltas["onefonb_vs_gpipe"]["regressed"]
    assert not deltas["pp_bubble_fraction"]["regressed"]
    # a schedule edit that grows the static bubble is flagged even when
    # the measured ratio holds (e.g. the host masked it)
    deltas = {d["metric"]: d
              for d in runlog.diff_records(self._rec(1.0, 3 / 19),
                                           self._rec(1.0, 3 / 11))}
    assert deltas["pp_bubble_fraction"]["regressed"]
    # small wobble inside both bands: clean
    deltas = {d["metric"]: d
              for d in runlog.diff_records(self._rec(1.0, 3 / 19),
                                           self._rec(0.95, 3 / 19))}
    assert not any(d["regressed"] for d in deltas.values())


def test_pp_schedule_code_backend_free(tmp_path):
  """Poisoned-platform trap over the schedule-selection/accounting code
  and the pp lint rule: importing pipeline_parallel, pricing schedules,
  computing the interleave permutation, and linting a call site must
  never initialize a JAX backend (same trap as tests/test_stager.py —
  a backend init would also take the chip)."""
  import os as os_lib
  import subprocess
  import sys

  repo_root = os_lib.path.dirname(
      os_lib.path.dirname(os_lib.path.abspath(__file__)))
  code = """
from tensor2robot_tpu.parallel import pipeline_parallel as pp
acc = pp.schedule_accounting(4, 8, 2)
assert acc["total_ticks"] == 19 and acc["idle_ticks_per_rank"] == 3
gpipe = pp.schedule_accounting(4, 8, 1)
assert acc["bubble_fraction"] < gpipe["bubble_fraction"]
assert pp.interleave_order(4, 2).tolist() == [0, 4, 1, 5, 2, 6, 3, 7]
from tensor2robot_tpu.analysis import pp_check
findings = pp_check.check_python_source(
    "x.py", "s = make_pipelined_train_step(f, l, o, m)\\n")
assert [f.rule for f in findings] == ["pp-schedule-unaudited"]
from tensor2robot_tpu.analysis import engine
engine.load_builtin_rules()
assert "pp-schedule-unaudited" in engine.catalog_text()
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("NO_BACKEND_OK")
"""
  env = {**os_lib.environ, "PYTHONPATH": repo_root,
         "JAX_PLATFORMS": "pp_schedule_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          cwd=repo_root, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "NO_BACKEND_OK" in result.stdout


class TestPipelinedModelVirtualStages:
  """The T2RModel carrier on the 1F1B schedule: num_virtual_stages=2
  through the generic step factory (configs/train_pipelined_1f1b.gin)."""

  def _model(self, **kwargs):
    import optax

    from tensor2robot_tpu.models import pipelined_model

    kwargs.setdefault("obs_size", 8)
    kwargs.setdefault("action_size", 3)
    kwargs.setdefault("hidden_size", 16)
    kwargs.setdefault("num_stages", 8)
    kwargs.setdefault("num_virtual_stages", 2)
    kwargs.setdefault("num_microbatches", 8)
    kwargs.setdefault("device_type", "cpu")
    kwargs.setdefault("optimizer_fn", lambda: optax.adam(3e-3))
    return pipelined_model.PipelinedRegressionModel(**kwargs)

  def test_1f1b_step_matches_sequential_step(self):
    """Same init, one train step: the interleaved schedule on a pp mesh
    produces the same loss and updated params as the sequential trunk
    (1F1B is a schedule, not a different function)."""
    from tensor2robot_tpu import specs as specs_lib
    from tensor2robot_tpu.models import pipelined_model

    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    results = {}
    for name, use_mesh in (("seq", False), ("pp", True)):
      model = self._model()
      features = specs_lib.make_random_numpy(
          model.get_feature_specification("train"), batch_size=16, seed=0)
      labels = specs_lib.make_random_numpy(
          model.get_label_specification("train"), batch_size=16, seed=1)
      if use_mesh:
        model.set_mesh(mesh)
        state, shardings = ts.create_train_state(
            model, jax.random.PRNGKey(0), features, mesh=mesh,
            rules=pipelined_model.pipeline_parallel_rules())
        step = ts.make_train_step(model, mesh=mesh, shardings=shardings,
                                  donate=False)
        f = mesh_lib.put_host_batch(mesh, features)
        l = mesh_lib.put_host_batch(mesh, labels)
      else:
        state, _ = ts.create_train_state(model, jax.random.PRNGKey(0),
                                         features)
        step = ts.make_train_step(model, donate=False)
        f, l = features, labels
      new_state, metrics = step(state, f, l)
      results[name] = (float(metrics["loss"]),
                       jax.device_get(new_state.params))
    assert results["pp"][0] == pytest.approx(results["seq"][0], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(results["pp"][1]),
                    jax.tree_util.tree_leaves(results["seq"][1])):
      np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

  def test_stage_params_sharded_and_loss_decreases(self):
    from tensor2robot_tpu import specs as specs_lib
    from tensor2robot_tpu.models import pipelined_model

    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    model = self._model()
    model.set_mesh(mesh)
    features = specs_lib.make_random_numpy(
        model.get_feature_specification("train"), batch_size=32, seed=0)
    labels = specs_lib.make_random_numpy(
        model.get_label_specification("train"), batch_size=32, seed=1)
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=mesh,
        rules=pipelined_model.pipeline_parallel_rules())
    # [S*v] stacked stage params sharded over the 4-wide pp axis
    w1 = state.params["stages_w1"]
    assert w1.shape[0] == 8
    assert w1.sharding.spec == PartitionSpec("pp", None, None), w1.sharding
    step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
    f = mesh_lib.put_host_batch(mesh, features)
    l = mesh_lib.put_host_batch(mesh, labels)
    first = None
    for _ in range(40):
      state, metrics = step(state, f, l)
      jax.block_until_ready(metrics)  # see conftest.py: one step in flight
      first = first if first is not None else float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))

  def test_set_mesh_rejects_chunk_mismatch(self):
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    model = self._model(num_stages=6)  # 6 != 4 ranks x 2 chunks
    with pytest.raises(ValueError, match="virtual"):
      model.set_mesh(mesh)


class TestVirtualStageSharpEdges:
  """Review-hardening pins: mesh-independent divisibility validation and
  the shard_pipeline_tree v>1 placement."""

  def test_model_rejects_indivisible_virtual_stages(self):
    from tensor2robot_tpu.models import pipelined_model

    with pytest.raises(ValueError, match="multiple"):
      pipelined_model.PipelinedRegressionModel(num_stages=6,
                                               num_virtual_stages=4)
    with pytest.raises(ValueError, match="multiple"):
      pipelined_model.PipelinedRegressionModel(num_stages=4,
                                               num_virtual_stages=0)

  def test_shard_pipeline_tree_places_any_stage_multiple(self):
    """A v>1 stage stack placed WITHOUT the num_virtual_stages argument
    still lands sharded over 'pp' (the silent-replication trap), while
    scalars and non-multiple leaves stay replicated."""
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    tree = {"v2_stack": jnp.zeros((8, 3)),   # S*v with v=2, arg omitted
            "v1_stack": jnp.zeros((4, 3)),
            "count": jnp.zeros(()),
            "odd": jnp.zeros((6, 3))}        # not a multiple of 4 ranks
    placed = pp.shard_pipeline_tree(tree, mesh, "pp")
    assert placed["v2_stack"].sharding.spec == PartitionSpec("pp")
    assert placed["v1_stack"].sharding.spec == PartitionSpec("pp")
    assert placed["count"].sharding.spec == PartitionSpec()
    assert placed["odd"].sharding.spec == PartitionSpec()

  def test_heterogeneous_rejects_wrong_stack_dim(self):
    """A [S, P_max] stack fed to an S*v-function call must raise, not
    silently clamp chunk gathers onto chunk 0's params."""
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1),
                                axis_names=("data", "pp", "model"))
    fns, unravels, sizes, stacked, micro = (
        TestHeterogeneousPipeline()._setup())
    with pytest.raises(ValueError, match="leading dim"):
      pp.pipelined_apply_heterogeneous(
          fns * 2, unravels * 2, sizes * 2, stacked, micro, mesh,
          num_virtual_stages=2)
