"""Pipeline-parallel engine.

Reference: ``deepspeed/runtime/pipe/engine.py`` (PipelineEngine:55 —
``train_batch:321`` executing TrainSchedule instruction streams via
``_exec_schedule:1357`` with P2P send/recv, ``_aggregate_total_loss:563``).

TPU-native execution: instead of a host loop dispatching P2P ops, the WHOLE
pipeline — M microbatches over P stages — is one jitted program:

- stage-stacked block parameters live sharded over the ``pipe`` mesh axis;
- a ``lax.scan`` over M + P - 1 ticks advances activations between neighbor
  stages with ``lax.ppermute`` (the reference's p2p.send/recv, but compiled:
  XLA overlaps the transfer with the next tick's compute);
- autodiff of the scan IS the backward pipeline — the reverse-order ticks with
  transposed ppermute reproduce the 1F1B dependency structure without an
  instruction interpreter, and gradient accumulation over microbatches falls
  out of the sum over ticks;
- first-batch tensor-meta exchange (reference ``_send_tensor_meta:854``) is
  unnecessary: shapes are static under jit.

The host-level instruction streams (schedule.py) remain as the semantic spec +
fallback executor; this engine is the fast path.

Model contract: a :class:`PipelineModule` whose built layers form
``[pre..., stack (homogeneous, length divisible by num_stages), post...]``.
``pre`` layers (e.g. embedding) run on the first stage, ``post`` (e.g. head)
on the last; the module's ``loss_fn(outputs, labels)`` closes the loss.
"""

from typing import Optional

import numpy as np

from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.logging import logger

PIPE_AXIS = groups.PIPE_AXIS


class PipelineError(Exception):
    ...


class PipelineEngine(DeepSpeedEngine):

    def __init__(self, args=None, model=None, mesh=None, config=None, config_class=None, **kwargs):
        assert isinstance(model, PipelineModule), "model must be a PipelineModule"
        import jax
        import jax.numpy as jnp

        self.pipeline_module = model
        # Pre-parse the config to learn the topology before the base engine runs.
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        cfg = config_class or DeepSpeedConfig(config, mesh=mesh)
        num_stages = model.num_stages

        if mesh is None and not groups.mesh_is_initialized():
            groups.initialize_mesh(model_parallel_size=cfg.tensor_parallel_size,
                                   pipe_parallel_size=num_stages,
                                   expert_parallel_size=cfg.expert_parallel_size,
                                   sequence_parallel_size=cfg.sequence_parallel_size)
        the_mesh = mesh if mesh is not None else groups.get_mesh()
        if the_mesh.shape.get(PIPE_AXIS, 1) != num_stages:
            raise PipelineError(f"mesh pipe axis {the_mesh.shape.get(PIPE_AXIS, 1)} != num_stages {num_stages}")

        # ---- build layers and split into pre / stack / post -----------------------
        layers = model.build_layers()
        rng = jax.random.PRNGKey(kwargs.get("rng_seed", 0) or 0)
        example = kwargs.pop("example_batch", None)
        if example is None:
            raise PipelineError("PipelineEngine requires example_batch=(inputs, labels) to "
                                "materialize layer parameters (shapes are static under XLA)")
        inputs, labels = example

        layer_params = []
        x = jnp.asarray(inputs)
        for i, layer in enumerate(layers):
            rng, sub = jax.random.split(rng)
            p = layer.init(sub, x)["params"]
            x = layer.apply({"params": p}, x)
            layer_params.append(p)
        out_struct = x

        structs = [jax.tree.structure(p) for p in layer_params]
        shapes = [tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(p)) for p in layer_params]

        def same(i, j):
            return (type(layers[i]) is type(layers[j]) and structs[i] == structs[j]
                    and shapes[i] == shapes[j])

        # longest homogeneous run = the stack
        best_lo, best_hi = 0, 1
        lo = 0
        for hi in range(1, len(layers) + 1):
            if hi == len(layers) or not same(lo, hi):
                if hi - lo > best_hi - best_lo:
                    best_lo, best_hi = lo, hi
                lo = hi
        stack_lo, stack_hi = best_lo, best_hi
        L = stack_hi - stack_lo
        if L % num_stages != 0:
            raise PipelineError(f"stack of {L} homogeneous layers not divisible by {num_stages} stages")

        self._pre_layers = layers[:stack_lo]
        self._stack_layer = layers[stack_lo]
        self._post_layers = layers[stack_hi:]
        self._num_stages = num_stages
        model.partition_layers(method="uniform")

        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *layer_params[stack_lo:stack_hi])
        params = {
            "pre": {str(i): p for i, p in enumerate(layer_params[:stack_lo])},
            "stack": stacked,
            "post": {str(i): p for i, p in enumerate(layer_params[stack_hi:])},
        }

        from jax.sharding import PartitionSpec as P
        specs = {
            "pre": jax.tree.map(lambda l: P(), params["pre"]),
            "stack": jax.tree.map(lambda l: P(PIPE_AXIS, *([None] * (l.ndim - 1))), params["stack"]),
            "post": jax.tree.map(lambda l: P(), params["post"]),
        }

        loss_closure = model.loss_fn or (lambda out, labels: out.mean())
        self._micro_batches = cfg.gradient_accumulation_steps
        pipeline_loss = self._make_pipeline_loss(loss_closure)

        kwargs.pop("model_parameters", None)
        kwargs.pop("loss_fn", None)
        kwargs.pop("param_specs", None)
        super().__init__(args=args,
                         model=None,
                         loss_fn=pipeline_loss,
                         model_parameters=params,
                         param_specs=specs,
                         mesh=the_mesh,
                         config=config,
                         config_class=config_class,
                         **kwargs)
        self._apply_gas_divisor = 1.0  # pipeline loss already averages microbatches

    # ------------------------------------------------------------------ loss --
    def _make_pipeline_loss(self, loss_closure):
        import jax
        import jax.numpy as jnp

        pre_layers = self._pre_layers
        stack_layer = self._stack_layer
        post_layers = self._post_layers
        P_stages = self._num_stages
        M = self._micro_batches

        def loss_fn(params, batch):
            inputs, labels = batch
            B = inputs.shape[0]
            assert B % M == 0, f"global batch {B} % microbatches {M} != 0"
            mb = B // M
            x_mb = inputs.reshape((M, mb) + inputs.shape[1:])
            y_mb = labels.reshape((M, mb) + labels.shape[1:])

            mesh = groups.get_mesh()
            from jax.sharding import PartitionSpec as PS

            dp_axes = tuple(ax for ax in groups.DATA_PARALLEL_AXES
                            if mesh.shape.get(ax, 1) > 1) or ("data", )
            param_specs = {
                "pre": jax.tree.map(lambda l: PS(), params["pre"]),
                "stack": jax.tree.map(lambda l: PS(PIPE_AXIS, *([None] * (l.ndim - 1))), params["stack"]),
                "post": jax.tree.map(lambda l: PS(), params["post"]),
            }
            batch_spec = PS(None, dp_axes)  # [M, mb@dp, ...]

            def pipelined(p, x_mb, y_mb):
                stage = jax.lax.axis_index(PIPE_AXIS)

                def embed(x):
                    for i, layer in enumerate(pre_layers):
                        x = layer.apply({"params": p["pre"][str(i)]}, x)
                    return x

                def head_loss(x, y):
                    for i, layer in enumerate(post_layers):
                        x = layer.apply({"params": p["post"][str(i)]}, x)
                    return loss_closure(x, y)

                def stage_fn(x):
                    def body(h, bp):
                        return stack_layer.apply({"params": bp}, h), None

                    return jax.lax.scan(body, x, p["stack"])[0]

                T = M + P_stages - 1
                act = jax.eval_shape(embed, jax.ShapeDtypeStruct(x_mb.shape[1:], x_mb.dtype))
                state = jnp.zeros(act.shape, act.dtype)
                losses = jnp.zeros((M, ), jnp.float32)

                def tick(carry, t):
                    state, losses = carry
                    recv = jax.lax.ppermute(state, PIPE_AXIS,
                                            [(i, i + 1) for i in range(P_stages - 1)])
                    t_in = jnp.clip(t, 0, M - 1)
                    x_t = jax.lax.dynamic_index_in_dim(x_mb, t_in, axis=0, keepdims=False)
                    # lax.cond on the per-shard stage id (valid under shard_map):
                    # only stage 0 pays for the embedding, only the last stage pays
                    # for the head + full-vocab loss — the module contract. Neither
                    # branch contains collectives, so per-stage divergence is safe.
                    inp = jax.lax.cond(stage == 0, lambda: embed(x_t), lambda: recv)
                    out = stage_fn(inp)
                    mb_idx = t - (P_stages - 1)
                    mb_safe = jnp.clip(mb_idx, 0, M - 1)
                    y_t = jax.lax.dynamic_index_in_dim(y_mb, mb_safe, axis=0, keepdims=False)
                    valid = (stage == P_stages - 1) & (mb_idx >= 0)
                    l_t = jax.lax.cond(valid,
                                       lambda: head_loss(out, y_t).astype(jnp.float32),
                                       lambda: jnp.float32(0.0))
                    losses = jnp.where(valid, losses.at[mb_safe].set(l_t), losses)
                    return (out, losses), None

                (state, losses), _ = jax.lax.scan(tick, (state, losses), jnp.arange(T))
                # last stage holds the loss; broadcast over pipe, average over data
                total = jax.lax.psum(jnp.where(stage == P_stages - 1, losses.mean(), 0.0), PIPE_AXIS)
                return jax.lax.pmean(total, dp_axes)

            return jax.shard_map(pipelined,
                                 mesh=mesh,
                                 in_specs=(param_specs, batch_spec, batch_spec),
                                 out_specs=PS(),
                                 check_vma=False)(params, x_mb, y_mb)

        return loss_fn

    # ------------------------------------------------------------- train API --
    def train_batch(self, data_iter=None, batch=None):
        """Reference pipe/engine.py:321 — consumes gradient_accumulation_steps
        micro-batches and performs one optimizer step."""
        import jax
        import jax.numpy as jnp
        if self.is_gradient_accumulation_boundary() is False:
            # raise BEFORE consuming the caller's iterator — micro-batches
            # pulled past a raise would be silently lost
            raise PipelineError(
                "set_gradient_accumulation_boundary(False) cannot suppress the "
                "optimizer step: the pipeline fuses schedule+step into one program. "
                "Drive micro-steps through the base engine instead.")
        if batch is None:
            assert data_iter is not None
            micro = [next(data_iter) for _ in range(self._micro_batches)]
            batch = jax.tree.map(lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *micro)

        batch = self.shard_batch(batch)
        rng = self._next_rng()
        loss, grads = self._grad_fn()(self.params, batch, rng, self.scale_state.cur_scale)
        lr = jnp.asarray(self._current_lr, jnp.float32)
        opt_in = self._offload.stage_in(self.opt_state)
        (self.params, self.opt_state, self.scale_state, norm,
         overflow) = self._apply_fn()(self.params, opt_in, grads, self.scale_state, lr)
        self.opt_state = self._offload.stage_out(self.opt_state)
        self._global_grad_norm = norm
        self._overflow_count = self._overflow_count + overflow.astype(jnp.int32)
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self.micro_steps += self._micro_batches
        self._step_lr_scheduler(overflow)
        return loss

    def eval_batch(self, data_iter=None, batch=None, compute_loss=True, reduce_output="avg"):
        """Reference pipe/engine.py eval_batch — forward-only InferenceSchedule."""
        import jax
        if batch is None:
            assert data_iter is not None
            micro = [next(data_iter) for _ in range(self._micro_batches)]
            batch = jax.tree.map(lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *micro)
        batch = self.shard_batch(batch)
        if "eval" not in self._compiled:
            self._compiled["eval"] = jax.jit(self.loss_fn)
        return self._compiled["eval"](self.params, batch)

    def forward(self, *a, **kw):
        raise PipelineError("Only train_batch() is accessible when using pipeline parallelism "
                            "(reference PipelineEngine raises the same)")

    def backward(self, *a, **kw):
        raise PipelineError("Only train_batch() is accessible when using pipeline parallelism")

    def step(self, *a, **kw):
        raise PipelineError("Only train_batch() is accessible when using pipeline parallelism")

    def is_gradient_accumulation_boundary(self):
        # train_batch fuses the whole 1F1B schedule + step into one program, so
        # every call IS a boundary — unless the user forced it off (reference
        # _force_grad_boundary, honored by set_gradient_accumulation_boundary)
        if self._gas_boundary_override is not None:
            return self._gas_boundary_override
        return True
