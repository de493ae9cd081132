"""FlaxModelOps — the learner's jit-compiled execution engine.

Replaces the reference's per-engine ModelOps (keras_model_ops.py:117-225,
pytorch_model_ops.py:23-172) with one JAX engine:

- local training runs **exactly N optimizer steps** as a cached jit-compiled
  step function (the reference converts steps→epochs and stops early with a
  ``StepCounter`` callback, keras_model_ops.py:131-138 — lossy; here N is N);
- FedProx is a proximal term added to the loss (∇ matches the reference's
  ``fed_prox.py`` update exactly);
- BatchNorm-style mutable state (``batch_stats``) is part of the federated
  model: it ships and aggregates with the weights;
- step wall-clock is measured post-compilation so the semi-sync scheduler
  sees steady-state timings (SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import inspect
import logging
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from metisfl_tpu.comm.messages import TrainParams
from metisfl_tpu.models.dataset import ArrayDataset
from metisfl_tpu.models.optimizers import make_optimizer
from metisfl_tpu.telemetry import profile as _tprofile
from metisfl_tpu.telemetry import runtime as _runtime
from metisfl_tpu.telemetry import trace as _ttrace
from metisfl_tpu.tensor.pytree import NamedTensors, _key_to_name

Pytree = Any

logger = logging.getLogger("metisfl_tpu.models")


@dataclass
class TrainOutput:
    # what ``train`` read back: the whole tree, or the named leaves the
    # caller asked for (``train(..., read=names)``)
    variables: Pytree | NamedTensors
    completed_steps: int
    completed_batches: int
    completed_epochs: float
    ms_per_step: float
    train_metrics: Dict[str, float]
    epoch_metrics: List[Dict[str, float]] = field(default_factory=list)
    # where ``train`` spent its own time, in milliseconds on the host's
    # clock (the learner's task waterfall, telemetry/profile.py): batches
    # drawn, stacked and placed; program call to the host sync; the
    # read-back of ``variables``. An engine that does not time itself
    # leaves them 0 and the learner counts that time as ``other``.
    feed_ms: float = 0.0
    steps_ms: float = 0.0
    readback_ms: float = 0.0
    readback_bytes: int = 0
    # what the module counted, a step (mean over the task's steps): for
    # each name ending ``_count`` that modules sow under ``intermediates``
    # the sum over the modules (``_sown_counts``). Empty for a module that
    # sows none.
    counts: Dict[str, float] = field(default_factory=dict)


def softmax_cross_entropy_loss(logits, y):
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def mse_loss(preds, y):
    return jnp.mean(jnp.square(preds - y))


_LOSSES = {
    "softmax_cross_entropy": softmax_cross_entropy_loss,
    "mse": mse_loss,
}


def _sown_counts(intermediates) -> Dict[str, Any]:
    """{name: sum over the modules} of what a forward pass sowed under a
    name ending ``_count`` (a routed layer's assignments on held experts,
    ``models/zoo/transformer.py``). An empty dict, and so the same
    program, for a module that sows none."""
    out: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        names = [k.key for k in path if hasattr(k, "key")]
        if names and str(names[-1]).endswith("_count"):
            out[names[-1]] = out.get(names[-1], 0.0) + leaf
    return out


def _accuracy(logits, y):
    return jnp.mean(jnp.argmax(logits, axis=-1) == y)


def _top5_accuracy(logits, y):
    k = min(5, logits.shape[-1])
    _, top = jax.lax.top_k(logits, k)
    return jnp.mean(jnp.any(top == y[..., None], axis=-1))


def _mse_metric(preds, y):
    return jnp.mean(jnp.square(preds.squeeze() - y))


def _mae_metric(preds, y):
    return jnp.mean(jnp.abs(preds.squeeze() - y))


# Evaluation metric registry: arbitrary per-task metric lists, matching the
# reference's free-form metric names (metis.proto:162-169) but typed and
# jit-compiled. Each metric maps (model outputs, labels) → scalar.
METRICS: Dict[str, Callable] = {
    "accuracy": _accuracy,
    "top5_accuracy": _top5_accuracy,
    "mse": _mse_metric,
    "mae": _mae_metric,
}


def register_metric(name: str, fn: Callable) -> None:
    """Register a custom eval metric ``fn(outputs, labels) -> scalar``."""
    METRICS[name] = fn


class FlaxModelOps:
    """Train/eval engine around one Flax module instance.

    ``module.apply`` convention: zoo modules accept an optional ``train``
    kwarg (dropout/batchnorm mode); plain modules without it work too.

    ``variables`` is the whole tree the engine holds, on the device once
    placed. Besides the whole-tree ``set_variables`` / ``get_variables()``
    a caller can place and read named leaves alone
    (``place_variables(named)``, ``get_variables(names)``,
    ``train(..., read=names)``): the leaves it does not name stay the
    device arrays they are. ``frozen_names()`` says which leaves no step
    can change, so a caller that placed them once need not place them
    again (the learner's ship-only rounds, learner/learner.py);
    ``variables_epoch`` moves whenever the tree is assigned whole, which
    is when such a caller has to start over.
    """

    def __init__(
        self,
        module,
        sample_input: np.ndarray,
        loss: str | Callable = "softmax_cross_entropy",
        rng_seed: int = 0,
        variables: Optional[Pytree] = None,
        mesh=None,
        partition_rules=None,
        trainable_regex: str = "",
    ):
        """``mesh`` + ``partition_rules`` enable in-learner sharded training
        (TP/FSDP via pjit — the Llama-LoRA ladder config; SURVEY.md §2.3):
        params are placed per the rules, batches are sharded over the data
        axes, and XLA inserts the collectives. ``trainable_regex`` freezes
        every param NOT matching it (LoRA fine-tuning: ``"lora_"``)."""
        self.module = module
        self._loss_name = loss if isinstance(loss, str) else getattr(loss, "__name__", "custom")
        self.loss_fn = _LOSSES[loss] if isinstance(loss, str) else loss
        self._rng = jax.random.PRNGKey(rng_seed)
        self.mesh = mesh
        self.partition_rules = list(partition_rules or [])
        self._trainable_regex = trainable_regex
        self.variables_epoch = 0
        # shape and type of an input, for whoever traces the forward
        # without running it (serving/gateway.py)
        self.sample_spec = jax.ShapeDtypeStruct(
            np.shape(sample_input),
            jax.dtypes.canonicalize_dtype(np.result_type(sample_input)))
        if variables is not None:
            self.variables = variables
        else:
            init_kwargs = {}
            if self._accepts_train_kwarg():
                init_kwargs["train"] = False
            self.variables = module.init(
                {"params": self._rng, "dropout": jax.random.fold_in(self._rng, 1)},
                jnp.asarray(sample_input), **init_kwargs)
        self._has_batch_stats = "batch_stats" in self.variables
        if self.mesh is not None:
            self.variables = self._shard(self.variables)
        self._step_cache: Dict[tuple, Callable] = {}
        self._eval_cache: Dict[Tuple[str, ...], Callable] = {}

    # -- sharded placement -------------------------------------------------
    def _shard(self, variables: Pytree) -> Pytree:
        from metisfl_tpu.parallel.sharding import tree_shardings
        shardings = tree_shardings(variables, self.mesh, self.partition_rules)
        # device_put handles host numpy directly, transferring each device
        # only its shard — no full-model staging on one device first
        return jax.device_put(variables, shardings)

    def _data_axis_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in ("dp", "fsdp")
                            if a in self.mesh.shape]))

    def _shard_batch(self, arr, batch_axis: int = 0):
        """Shard the batch dimension (``batch_axis``) over the mesh's data
        axes; a leading scan axis (batch_axis=1) stays replicated."""
        from jax.sharding import NamedSharding, PartitionSpec
        data_axes = tuple(a for a in ("dp", "fsdp") if a in self.mesh.shape)
        n = self._data_axis_size()
        if n > 1 and arr.shape[batch_axis] % n:
            raise ValueError(
                f"batch of {arr.shape[batch_axis]} examples is not divisible "
                f"by the mesh data axes {data_axes} (size {n}); pick a "
                f"batch_size that is a multiple of {n} and shards with >= "
                "batch_size examples")
        spec = PartitionSpec(*([None] * batch_axis),
                             data_axes if data_axes else None)
        return jax.device_put(jnp.asarray(arr), NamedSharding(self.mesh, spec))

    # -- module introspection ---------------------------------------------
    def _accepts_train_kwarg(self) -> bool:
        try:
            sig = inspect.signature(self.module.__call__)
            return "train" in sig.parameters
        except (TypeError, ValueError):  # pragma: no cover
            return False

    def _apply(self, variables, x, train: bool, rngs=None,
               collect_intermediates: bool = False):
        kwargs = {}
        if self._accepts_train_kwarg():
            kwargs["train"] = train
        mutable = []
        if train and self._has_batch_stats:
            mutable.append("batch_stats")
        if collect_intermediates:
            # sown auxiliary losses (e.g. the MoE router's load-balance term)
            mutable.append("intermediates")
        return self.module.apply(variables, x, rngs=rngs,
                                 mutable=mutable or False, **kwargs)

    # -- cost accounting ---------------------------------------------------
    def param_count(self) -> int:
        """Trainable parameter count (``params`` collection leaves)."""
        if not hasattr(self, "_param_count"):
            leaves = jax.tree.leaves(self.variables.get("params", {}))
            self._param_count = int(sum(np.size(l) for l in leaves))
        return self._param_count

    def step_flops(self, batch_size: int) -> float:
        """Estimated FLOPs for one optimizer step at ``batch_size``: the
        dense-layer approximation 6·params·batch (2 forward + 4 backward
        matmul FLOPs per parameter per example). The MFU numerator for
        the performance observatory's achieved-utilization gauge —
        an estimate per example, not per token, and not an XLA cost-model
        readout (the benchmark counts in ``benchmark/lib/flops.py``)."""
        return 6.0 * self.param_count() * max(1, int(batch_size))

    # -- weights I/O -------------------------------------------------------
    @property
    def variables(self) -> Pytree:
        return self._variables

    @variables.setter
    def variables(self, tree: Pytree) -> None:
        # the whole tree assigned (``set_variables``, or a caller's own
        # ``ops.variables = ...``): whoever placed leaves once and counted
        # on their staying has to place them again. ``train`` and
        # ``place_variables`` write ``_variables`` and leave the epoch.
        self._variables = tree
        self.variables_epoch += 1

    def _named_leaves(self):
        flat, treedef = jax.tree_util.tree_flatten_with_path(self._variables)
        return [(_key_to_name(path), leaf) for path, leaf in flat], treedef

    def frozen_names(self) -> frozenset:
        """Wire names of the leaves no train step can change: the params
        the freeze mask (``trainable_regex``) labels ``freeze``. Empty
        without a mask; ``batch_stats`` is never among them (the step
        mutates it)."""
        if not self._trainable_regex:
            return frozenset()
        import re
        # the mask matches names below ``params`` (_make_step)
        return frozenset(
            name for name, _ in self._named_leaves()[0]
            if name.startswith("params/") and not re.search(
                self._trainable_regex, name[len("params/"):]))

    def get_variables(self, names=None) -> Pytree | NamedTensors:
        """The whole tree on the host; with ``names`` those leaves alone,
        as named tensors in tree order (nothing else is read back)."""
        if names is None:
            return jax.device_get(self._variables)
        picked = [(n, leaf) for n, leaf in self._named_leaves()[0]
                  if n in names]
        host = jax.device_get([leaf for _, leaf in picked])
        return [(n, a) for (n, _), a in zip(picked, host)]

    def set_variables(self, variables: Pytree) -> None:
        if self.mesh is not None:
            self.variables = self._shard(variables)
        else:
            self.variables = jax.tree.map(jnp.asarray, variables)

    def place_variables(self, named: NamedTensors) -> None:
        """Replace the named leaves of the tree the engine holds; every
        other leaf stays the device array it is (no second copy of the
        tree, nothing placed twice)."""
        new = dict(named)
        leaves, treedef = self._named_leaves()
        unknown = sorted(set(new) - {n for n, _ in leaves})
        if unknown:
            raise KeyError(f"no such leaves in the model: {unknown[:5]}")
        if self.mesh is not None:
            from metisfl_tpu.parallel.sharding import tree_shardings
            shardings = jax.tree.leaves(tree_shardings(
                self._variables, self.mesh, self.partition_rules))
            placed = [jax.device_put(new[n], shardings[i]) if n in new
                      else leaf for i, (n, leaf) in enumerate(leaves)]
        else:
            placed = [jnp.asarray(new[n]) if n in new else leaf
                      for n, leaf in leaves]
        self._variables = jax.tree_util.tree_unflatten(treedef, placed)

    # -- training ----------------------------------------------------------
    def _cfg_key(self, params_cfg: TrainParams) -> tuple:
        return (
            params_cfg.optimizer,
            float(params_cfg.learning_rate),
            tuple(sorted((params_cfg.optimizer_kwargs or {}).items())),
            float(params_cfg.proximal_mu),
            float(params_cfg.moe_aux_weight),
            self._loss_name,
        )

    def _make_step(self, params_cfg: TrainParams):
        key = self._cfg_key(params_cfg)
        if key in self._step_cache:
            return self._step_cache[key]

        tx = make_optimizer(params_cfg.optimizer, params_cfg.learning_rate,
                            params_cfg.optimizer_kwargs)
        if self._trainable_regex:
            import re as _re

            regex = self._trainable_regex

            def _labels(params):
                flat, treedef = jax.tree_util.tree_flatten_with_path(params)
                labels = ["train" if _re.search(regex, _key_to_name(p))
                          else "freeze" for p, _ in flat]
                if "train" not in labels:
                    raise ValueError(
                        f"trainable_regex {regex!r} matches no params — "
                        "training would silently be a no-op (did you forget "
                        "lora_rank > 0?)")
                return jax.tree_util.tree_unflatten(treedef, labels)

            # multi_transform + set_to_zero actually freezes; optax.masked
            # would pass the raw gradients through for unmasked leaves
            tx = optax.multi_transform(
                {"train": tx, "freeze": optax.set_to_zero()}, _labels)
        mu = float(params_cfg.proximal_mu)
        has_bs = self._has_batch_stats
        loss_fn = self.loss_fn

        aux_weight = float(params_cfg.moe_aux_weight)

        def loss_and_aux(params, batch_stats, global_params, x, y, rng):
            variables = {"params": params}
            if has_bs:
                variables["batch_stats"] = batch_stats
            logits, mutated = self._apply(variables, x, train=True,
                                          rngs={"dropout": rng},
                                          collect_intermediates=True)
            new_bs = mutated.get("batch_stats", batch_stats)
            loss = loss_fn(logits, y)
            # sown auxiliary losses enter the objective (Switch MoE
            # load-balancing — without this term the router can collapse
            # onto one expert and capacity-drop most tokens)
            if aux_weight > 0.0:
                aux_terms = [
                    leaf for path, leaf in
                    jax.tree_util.tree_flatten_with_path(
                        mutated.get("intermediates", {}))[0]
                    if "aux_loss" in jax.tree_util.keystr(path)
                ]
                if aux_terms:
                    loss = loss + aux_weight * sum(aux_terms)
            if mu > 0.0:
                prox = sum(
                    jnp.sum(jnp.square(p - p0))
                    for p, p0 in zip(jax.tree.leaves(params),
                                     jax.tree.leaves(global_params))
                )
                loss = loss + 0.5 * mu * prox
            counts = _sown_counts(mutated.get("intermediates", {}))
            return loss, (logits, new_bs, counts)

        def step(params, batch_stats, opt_state, global_params, grad_offset,
                 x, y, rng):
            (loss, (logits, new_bs, counts)), grads = jax.value_and_grad(
                loss_and_aux, has_aux=True)(params, batch_stats, global_params,
                                            x, y, rng)
            if jax.tree_util.tree_leaves(grad_offset):
                # control-variate correction (SCAFFOLD: c - c_i); the empty
                # tree compiles to the uncorrected program — structure is
                # static at trace time
                grads = jax.tree.map(
                    lambda g, o: g + jnp.asarray(o, g.dtype),
                    grads, grad_offset)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            acc = _accuracy(logits, y)
            return params, new_bs, opt_state, loss, acc, counts

        compiled = _runtime.monitored_jit(step, name="train.step",
                                          donate_argnums=(0, 1, 2))
        self._step_cache[key] = (compiled, tx, step)
        return self._step_cache[key]

    def _make_scan(self, params_cfg: TrainParams, chunk: int):
        """``chunk`` optimizer steps as ONE compiled program: lax.scan over
        stacked batches with the training state as carry. One dispatch and
        one host sync per chunk instead of per step — on TPU the difference
        is pure launch overhead. Same math as the per-step path: the scan
        body IS the per-step function."""
        key = self._cfg_key(params_cfg) + ("scan", chunk)
        cached = self._step_cache.get(key)
        if cached is not None:
            return cached
        _, tx, step = self._make_step(params_cfg)

        def scan_steps(params, batch_stats, opt_state, global_params,
                       grad_offset, rng0, step_ids, xs, ys):
            # the rng rides the carry and folds with the global step index
            # INSIDE the program — same chained fold_in sequence as the
            # per-step path, but zero extra host dispatches per step
            def body(carry, batch):
                params, batch_stats, opt_state, rng = carry
                x, y, step_id = batch
                rng = jax.random.fold_in(rng, step_id)
                params, batch_stats, opt_state, loss, acc, counts = step(
                    params, batch_stats, opt_state, global_params,
                    grad_offset, x, y, rng)
                return ((params, batch_stats, opt_state, rng),
                        (loss, acc, counts))

            (params, batch_stats, opt_state, rng), (losses, accs, counts) = (
                jax.lax.scan(body, (params, batch_stats, opt_state, rng0),
                             (xs, ys, step_ids)))
            return params, batch_stats, opt_state, rng, losses, accs, counts

        compiled = _runtime.monitored_jit(scan_steps,
                                          name="train.scan_steps",
                                          donate_argnums=(0, 1, 2))
        self._step_cache[key] = (compiled, tx)
        return self._step_cache[key]

    def train(self, dataset: ArrayDataset, params_cfg: TrainParams,
              cancel_event=None, grad_offset=None, read=None) -> TrainOutput:
        """``grad_offset``: optional params-shaped tree ADDED to every
        step's gradients (SCAFFOLD control-variate correction c - c_i;
        None = uncorrected — identical compiled program). ``read``: the
        names of the leaves to read back into ``TrainOutput.variables``
        (``get_variables(names)``); None reads the whole tree."""
        steps_per_epoch = max(1, len(dataset) // max(1, params_cfg.batch_size))
        if params_cfg.local_steps > 0:
            total_steps = params_cfg.local_steps
        else:
            total_steps = max(1, int(math.ceil(
                params_cfg.local_epochs * steps_per_epoch)))

        compiled, tx, _ = self._make_step(params_cfg)
        params = self._variables["params"]
        batch_stats = self._variables.get("batch_stats", {})
        # FedProx anchors to a non-donated copy of the round-start params;
        # without FedProx an empty tree avoids aliasing the donated params.
        global_params = (jax.tree.map(jnp.copy, params)
                         if params_cfg.proximal_mu > 0 else {})
        grad_offset = {} if grad_offset is None else grad_offset
        opt_state = tx.init(params)

        losses: List[float] = []
        accs: List[float] = []
        epoch_metrics: List[Dict[str, float]] = []
        epoch_losses: List[Any] = []
        step_times: List[float] = []
        # the module's counters, a chunk's or a step's as the program
        # returned them: left on the device until the task's end (no sync
        # and no program of their own), summed on the host there
        counted: Dict[str, list] = {}

        def _add_counts(counts) -> None:
            for name, value in counts.items():
                counted.setdefault(name, []).append(value)

        completed = 0
        rng = self._rng
        # the task waterfall's inner tiles (TrainOutput.feed_ms/steps_ms):
        # seconds summed over chunks and steps, and where the first of
        # each began on this clock
        wall0, perf0 = time.time(), time.perf_counter()
        feed_s = steps_s = 0.0
        feed_at = steps_at = None

        place = (self._shard_batch if self.mesh is not None
                 else lambda arr, batch_axis=0: jnp.asarray(arr))
        stream = dataset.infinite_batches(params_cfg.batch_size)
        chunk = max(1, int(params_cfg.scan_chunk))

        def _flush_epoch(force: bool = False) -> None:
            nonlocal epoch_losses
            if epoch_losses and (
                    force or completed % steps_per_epoch == 0
                    or completed == total_steps):
                ls = [float(l) for l, _ in epoch_losses]
                as_ = [float(a) for _, a in epoch_losses]
                epoch_metrics.append({"loss": float(np.mean(ls)),
                                      "accuracy": float(np.mean(as_))})
                losses.extend(ls)
                accs.extend(as_)
                epoch_losses = []

        # jax.profiler capture lifecycle for this task: one reusable
        # handle (telemetry/profile.py) with idempotent, exception-safe
        # stop and a unique per-capture session dir — replaces the three
        # start/stop bookkeeping sites this loop used to carry
        tracer = _tprofile.device_tracer(params_cfg.profile_dir)
        fallback_time: Optional[float] = None
        try:
            if chunk > 1 and total_steps >= chunk:
                scan_compiled, _ = self._make_scan(params_cfg, chunk)
                n_chunks = total_steps // chunk
                for chunk_idx in range(n_chunks):
                    if cancel_event is not None and cancel_event.is_set():
                        break
                    # second chunk = first steady-state program execution;
                    # a single-chunk run has no steady-state chunk to trace
                    # (the remainder loop below still traces when it runs)
                    chunk_profiling = (chunk_idx == 1 and tracer.start())
                    t_feed = time.perf_counter()
                    xs, ys = [], []
                    for _ in range(chunk):
                        x, y = next(stream)
                        xs.append(x)
                        ys.append(y)
                    xs = place(np.stack(xs), batch_axis=1)
                    ys = place(np.stack(ys), batch_axis=1)
                    step_ids = jnp.arange(completed, completed + chunk,
                                          dtype=jnp.uint32)
                    t0 = time.perf_counter()
                    feed_s += t0 - t_feed
                    if feed_at is None:
                        feed_at, steps_at = t_feed, t0
                    (params, batch_stats, opt_state, rng, c_losses, c_accs,
                     c_counts) = scan_compiled(
                         params, batch_stats, opt_state, global_params,
                         grad_offset, rng, step_ids, xs, ys)
                    _add_counts(c_counts)
                    c_losses = np.asarray(c_losses)
                    c_accs = np.asarray(c_accs)   # host sync, once per chunk
                    chunk_s = time.perf_counter() - t0
                    steps_s += chunk_s
                    if chunk_idx > 0 and not chunk_profiling:
                        step_times.extend([chunk_s / chunk] * chunk)
                    elif n_chunks == 1 or chunk_profiling:
                        # compile- or profiler-contaminated; used only if no
                        # clean sample lands anywhere in the run
                        fallback_time = chunk_s / chunk
                    if chunk_profiling:
                        tracer.stop()
                    for loss, acc in zip(c_losses, c_accs):
                        completed += 1
                        epoch_losses.append((loss, acc))
                        _flush_epoch()
                remaining = (total_steps - completed
                             if not (cancel_event is not None
                                     and cancel_event.is_set()) else 0)
            else:
                remaining = total_steps

            # per-step path: the whole run (chunk == 1), the scan remainder
            # (total_steps % chunk), or the whole run again when
            # total_steps < chunk made the scan path skip itself
            profile_from = completed + (1 if remaining > 1 else 0)
            profile_until = profile_from + max(1, params_cfg.profile_steps)
            per_step_runs = 0
            for _ in range(remaining):
                if cancel_event is not None and cancel_event.is_set():
                    break
                if completed == profile_from:
                    tracer.start()  # no-op when already captured or inert
                t_feed = time.perf_counter()
                x, y = next(stream)
                rng = jax.random.fold_in(rng, completed)
                # one batch is placed inside the timed call, as it always
                # was on this path: here ``feed`` is the draw alone
                t0 = time.perf_counter()
                feed_s += t0 - t_feed
                if feed_at is None:
                    feed_at, steps_at = t_feed, t0
                params, batch_stats, opt_state, loss, acc, counts = compiled(
                    params, batch_stats, opt_state, global_params,
                    grad_offset, place(x), place(y), rng)
                _add_counts(counts)
                per_step_runs += 1
                if per_step_runs > 1 or (remaining == 1 and not step_times):
                    # the per-step program's first execution pays its jit
                    # compile — keep it out of steady-state timing (unless
                    # it would be the only sample in the whole run)
                    jax.block_until_ready(loss)
                    step_times.append(time.perf_counter() - t0)
                if tracer.active and completed + 1 >= profile_until:
                    jax.block_until_ready(loss)
                    tracer.stop()
                completed += 1
                epoch_losses.append((loss, acc))
                _flush_epoch()
                steps_s += time.perf_counter() - t0

            if tracer.active:
                jax.block_until_ready(loss)
        finally:
            # exception-safe: a trace left open would wedge the NEXT
            # task's capture and leak the profiler session
            tracer.stop()

        # the per-step path's last steps are still in flight: reading
        # their losses is the sync, and so part of the steps
        t_sync = time.perf_counter()
        _flush_epoch(force=True)

        new_vars = {"params": params}
        if self._has_batch_stats:
            new_vars["batch_stats"] = batch_stats
        # the engine's own write: the leaves the step cannot change are
        # what they were, so the epoch stays
        self._variables = new_vars
        self._rng = rng

        if not step_times and fallback_time is not None:
            step_times = [fallback_time]
        ms_per_step = float(np.median(step_times) * 1e3) if step_times else 0.0
        t_read = time.perf_counter()
        steps_s += t_read - t_sync
        variables = self.get_variables(read)
        readback_s = time.perf_counter() - t_read
        # (the names of a named read are leaves too, of no bytes)
        read_bytes = sum(getattr(leaf, "nbytes", 0)
                         for leaf in jax.tree.leaves(variables))
        if feed_at is not None:
            _ttrace.event("train.feed", feed_s,
                          start=wall0 + feed_at - perf0)
            _ttrace.event("train.steps", steps_s,
                          start=wall0 + steps_at - perf0,
                          attrs={"steps": completed})
        _ttrace.event("train.readback", readback_s,
                      start=wall0 + t_read - perf0,
                      attrs={"bytes": read_bytes})
        return TrainOutput(
            variables=variables,
            completed_steps=completed,
            completed_batches=completed,
            completed_epochs=completed / steps_per_epoch,
            ms_per_step=ms_per_step,
            train_metrics={
                "loss": float(np.mean(losses)) if losses else float("nan"),
                "accuracy": float(np.mean(accs)) if accs else float("nan"),
            },
            epoch_metrics=epoch_metrics,
            feed_ms=feed_s * 1e3,
            steps_ms=steps_s * 1e3,
            readback_ms=readback_s * 1e3,
            readback_bytes=read_bytes,
            counts={name: float(sum(np.sum(np.asarray(v)) for v in values))
                    / max(1, completed)
                    for name, values in counted.items()},
        )

    # -- inference ---------------------------------------------------------
    def infer(self, x: np.ndarray, batch_size: int = 256,
              variables: Optional[Pytree] = None) -> np.ndarray:
        """Batched forward pass → stacked model outputs (logits/predictions).

        The reference's third ModelOps task type (model_ops.py ``infer``,
        learner.py:311-330); here one cached jit forward reused across calls.
        Passing ``variables`` runs inference on an explicit model without
        touching the engine's training slot.
        """
        if not hasattr(self, "_infer_compiled"):
            self._infer_compiled = _runtime.monitored_jit(
                lambda v, xb: self._apply(v, xb, train=False),
                name="infer")
        if variables is None:
            variables = self.variables
        elif self.mesh is not None:
            variables = self._shard(variables)
        else:
            variables = jax.tree.map(jnp.asarray, variables)
        outs = []
        for start in range(0, len(x), batch_size):
            batch = jnp.asarray(x[start : start + batch_size])
            outs.append(np.asarray(self._infer_compiled(variables, batch)))
        if not outs:
            return np.zeros((0,), np.float32)
        return np.concatenate(outs, axis=0)

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 variables: Optional[Pytree] = None,
                 **sampling) -> np.ndarray:
        """Autoregressive decoding on a causal-LM module (KV-cache decode,
        one jitted program per shape/config — models/generate.py). Sampling
        kwargs: ``temperature``, ``top_k``, ``top_p``, ``eos_id``, ``pad_id``, ``rng``,
        ``max_len``. Sampled calls without an explicit ``rng`` advance the
        engine's own rng, so repeated requests draw different streams."""
        from metisfl_tpu.models.generate import generate as _generate

        if variables is None:
            variables = self.variables
        if sampling.get("temperature", 0.0) > 0.0 \
                and sampling.get("rng") is None:
            # a DEDICATED generation stream: advancing self._rng here would
            # make training dropout depend on how many inference requests
            # were served in between (breaking cross-learner train
            # reproducibility)
            if not hasattr(self, "_gen_rng"):
                self._gen_rng = jax.random.fold_in(self._rng, 0x6E67)
            self._gen_rng, sampling["rng"] = jax.random.split(self._gen_rng)
        return np.asarray(_generate(self.module, variables,
                                    np.asarray(prompt, np.int32),
                                    max_new_tokens, **sampling))

    # -- evaluation --------------------------------------------------------
    def _make_eval(self, metric_names: Tuple[str, ...]):
        cached = self._eval_cache.get(metric_names)
        if cached is not None:
            return cached
        loss_fn = self.loss_fn
        unknown = [m for m in metric_names if m not in METRICS]
        if unknown:
            raise ValueError(
                f"unknown eval metrics {unknown}; registered: {sorted(METRICS)}"
                " (add custom ones via metisfl_tpu.models.ops.register_metric)")
        fns = [(name, METRICS[name]) for name in metric_names]

        def eval_step(variables, x, y):
            logits = self._apply(variables, x, train=False)
            vals = {"loss": loss_fn(logits, y)}
            for name, fn in fns:
                vals[name] = fn(logits, y)
            return vals

        compiled = _runtime.monitored_jit(eval_step, name="eval.step")
        self._eval_cache[metric_names] = compiled
        return compiled

    def evaluate(self, dataset: ArrayDataset, batch_size: int = 256,
                 metrics: Optional[List[str]] = None,
                 variables: Optional[Pytree] = None) -> Dict[str, float]:
        """Evaluate ``variables`` (default: the engine's current model).

        ``metrics`` selects from the METRICS registry (loss is always
        reported; unregistered names are skipped with a warning, matching the
        reference's tolerance of free-form metric lists, metis.proto:162-169
        — eval runs on fire-and-forget threads, so raising here would make
        evaluations silently vanish). Passing variables explicitly lets an
        eval run concurrently with training without racing on the engine's
        model slot.
        """
        requested = [m for m in (metrics or ["accuracy"]) if m != "loss"]
        unknown = [m for m in requested if m not in METRICS]
        if unknown:
            logger.warning("skipping unregistered eval metrics %s "
                           "(registered: %s)", unknown, sorted(METRICS))
        names = tuple(m for m in requested if m in METRICS)
        eval_step = self._make_eval(names)
        if variables is None:
            variables = self.variables
        elif self.mesh is not None:
            # keep eval on the same sharded layout as training (an
            # unsharded placement would stage the full model on one device)
            variables = self._shard(variables)
        else:
            variables = jax.tree.map(jnp.asarray, variables)
        totals = {name: 0.0 for name in ("loss",) + names}
        count = 0
        for x, y in dataset.batches(batch_size, shuffle=False):
            n = x.shape[0]
            vals = eval_step(variables, jnp.asarray(x), jnp.asarray(y))
            for name, v in vals.items():
                totals[name] += float(v) * n
            count += n
        if count == 0:
            return {}
        return {name: total / count for name, total in totals.items()}
