"""The model-zoo contract.

The reference selects user model components by name from a zoo module
(elasticdl/python/common/model_utils.py:135-192: model/loss/optimizer/feed/
eval_metrics_fn).  Here the contract is a single ``ModelSpec`` value the
module builds via an exported ``model_spec(**kwargs)`` function — pure
functions + pytrees, so every field composes with jit/grad/shard_map.

Conventions:
 - ``loss_fn(outputs, labels)`` returns a *per-example* loss vector; the
   trainer applies padding masks and reduces.  (Static batch shapes for XLA:
   partial minibatches are padded, never shape-changed.)
 - ``feed(records)`` turns a list of reader records into a tuple of ndarrays
   ``(inputs..., labels)`` forming one batch.
"""

import dataclasses
import importlib
import typing


@dataclasses.dataclass
class ModelSpec:
    name: str
    init_fn: typing.Callable        # rng -> params pytree
    apply_fn: typing.Callable       # (params, inputs, train) -> outputs
    loss_fn: typing.Callable        # (outputs, labels) -> per-example loss
    optimizer: typing.Any           # optax.GradientTransformation
    feed: typing.Callable           # [records] -> (inputs, labels) ndarrays
    eval_metrics_fn: typing.Callable = None  # () -> {name: Metric}
    prediction_outputs_processor: typing.Any = None
    callbacks: list = dataclasses.field(default_factory=list)
    # Optional: names of embedding tables served by the parameter server
    # (the sparse path); empty for pure dense models.
    ps_embedding_infos: list = dataclasses.field(default_factory=list)
    # PS-side optimizer as (opt_type, opt_args) flag strings — the analog
    # of the reference's Keras-optimizer -> Go-PS-flags mapping
    # (model_utils.py:227-254).
    ps_optimizer: tuple = ("sgd", "learning_rate=0.1")
    # Optional: ``outputs -> {name: array}`` of small additive per-step
    # statistics taken from the training ``apply_fn``'s outputs (an
    # MoE's router load).  CollectiveTrainer carries them out of the
    # step beside the loss (summed over accumulation microbatches) as
    # ``last_step_stats``; None adds nothing to the step program.
    step_stats_fn: typing.Callable = None


def load_model_spec(module_name, model_params="", **kwargs):
    """Import a zoo module and build its ModelSpec.

    ``module_name`` may be a short zoo name ("mnist") or a full dotted
    path; ``model_params`` is a "k=v;k=v" string merged into kwargs
    (ints/floats parsed; the reference's --model_def/--model_params
    mechanism, model_utils.py:135-192).
    """
    if model_params:
        from elasticdl_tpu.utils.args import parse_opt_args

        for key, value in parse_opt_args(model_params).items():
            if isinstance(value, float) and value.is_integer():
                value = int(value)
            kwargs.setdefault(key, value)
    if "." not in module_name:
        module_name = "elasticdl_tpu.models." + module_name
    module = importlib.import_module(module_name)
    if not hasattr(module, "model_spec"):
        raise ValueError(
            "%s does not export model_spec(**kwargs)" % module_name
        )
    return module.model_spec(**kwargs)
