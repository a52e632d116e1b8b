"""The product against a configuration's plain reference
(``benchmark/reference/<name>.py``): the loss and every gradient leaf,
written once for the model tests that make the comparison.  No test
lives here.

A ``Case`` is drawn once a process (``rehearsal``: the configuration at
its rehearsal size, inputs by the reference's own ``inputs``; ``tiny``:
a test's own widths), the reference's loss and gradients are one
``jax.jit(jax.value_and_grad(..))`` a case (``wanted``), the product's
are compiled in the kernel mode the caller names (``product``), and
``check`` holds one to the other with the caller's tolerances.  A new
configuration's model test is a call of ``check`` (ROADMAP C16 (b)).
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from benchmark.lib.runner import merge, params_string
from elasticdl_tpu.models import transformer as tfm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops.mode import SWITCH
from tests.test_remat_keep import _pallas_calls


@functools.lru_cache(maxsize=None)
def configuration(name, size="rehearsal"):
    """``benchmark/configs/<name>.json`` with its ``size`` laid over it
    (None: as published)."""
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           name + ".json")) as fh:
        published = json.load(fh)
    return merge(published, published[size]) if size else published


def _frozen(model):
    return tuple(sorted(model.items()))


@functools.lru_cache(maxsize=None)
def _spec(build, model):
    return build(dict(model))


def _of_file(model):
    return load_model_spec("transformer", model_params=params_string(model))


def _of_widths(model):
    return tfm.model_spec(**model)


def spec_of(name, **over):
    """The ModelSpec of the configuration's rehearsal model, ``over``
    laid over its ``model_params``."""
    return _spec(_of_file, _frozen(dict(
        configuration(name)["cli"]["model_params"], **over)))


def loss_of(spec, tokens, stats=False):
    """p -> the product's mean loss over ``tokens`` (``stats``: beside
    its step statistics)."""
    def total(p):
        out = spec.apply_fn(p, tokens, True)
        loss = spec.loss_fn(out, tokens).mean()
        return (loss, spec.step_stats_fn(out) if spec.step_stats_fn
                else ()) if stats else loss

    return total


class Case:
    """A reference, ``loss``'s keywords, the model's parameters as its
    ``model_params``, and the seeded (params, tokens) both sides take.
    Hashed by identity: the functions that draw one are cached."""

    def __init__(self, ref, shape, build, model, params, tokens):
        self.ref, self.shape, self.build = ref, shape, build
        self.model, self.params, self.tokens = model, params, tokens

    def spec(self, **over):
        """The product's ModelSpec, with ``over`` laid over the case's
        ``model_params`` (``remat``, ``dtype``, a depth)."""
        return _spec(self.build, _frozen(dict(self.model, **over)))

    def parts(self, **over):
        """(``spec(**over)``, params, tokens)."""
        return self.spec(**over), self.params, self.tokens

    def reference(self, **how):
        """p -> the reference's (loss, everything else ``loss`` hands
        back), the module's loss beside the main one at its weight;
        ``how`` is laid over ``loss``'s keywords."""
        shape = dict(self.shape, **how)
        weight = shape.get("mtp_weight")

        def total(p):
            main, *rest = self.ref.loss(p, self.tokens, **shape)
            if weight is not None:
                main = main + weight * rest[0]
            return main.mean(), rest

        return total


@functools.lru_cache(maxsize=None)
def rehearsal(name, seed=3, edit=None, *args, model=(), **over):
    """The configuration at its rehearsal size as the chip's comparison
    draws it (``inputs`` of its reference), each sequence beside its
    reverse; ``edit(params, *args)`` where a file wants other
    parameters; ``over`` laid over the file's keys and ``model`` (pairs)
    over its ``model_params`` where a case wants another depth."""
    ref = manifest.load_named("reference", name)
    config = merge(configuration(name), over)
    model = dict(config["cli"]["model_params"], **dict(model))
    params, tokens = ref.inputs(
        config, jax.jit(_spec(_of_file, _frozen(model)).init_fn)(
            jax.random.PRNGKey(seed)), np.random.default_rng(seed))
    return Case(ref, ref.shape_of(config), _of_file, model,
                edit(params, *args) if edit else params,
                jnp.concatenate([tokens, tokens[:, ::-1]]))


@functools.lru_cache(maxsize=None)
def _tiny(name, shape_of, model, batch, seed, config):
    ref = manifest.load_named("reference", name)
    spec = _spec(_of_widths, model)
    cfg = spec.config
    params, _ = ref.inputs(
        dict(config, vocab_size=cfg.vocab_size, seq_len=4),
        jax.jit(spec.init_fn)(jax.random.PRNGKey(seed)),
        np.random.default_rng(seed))
    tokens = jnp.asarray(np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len)), jnp.int32)
    return Case(ref, shape_of(cfg), _of_widths, dict(model), params, tokens)


def tiny(name, shape_of, model, batch=2, seed=3, **config):
    """A model of a test's own widths (``model``: ``model_spec``'s
    keywords but ``remat``, the product's to choose) with the parameters
    ``name``'s reference draws and ``batch`` sequences; ``shape_of(cfg)``
    gives ``loss``'s keywords, ``config`` what else ``inputs`` reads."""
    return _tiny(name, shape_of, _frozen(model), batch, seed,
                 _frozen(config))


@functools.lru_cache(maxsize=None)
def wanted(case):
    """((loss, the rest), gradients) of the reference: one compile a
    case, whatever the modes its product runs in."""
    return jax.jit(jax.value_and_grad(case.reference(), has_aux=True))(
        case.params)


def product(case, mode="off", **over):
    """((loss, step statistics), gradients) of the product traced in
    kernel ``mode``, its spec the case's with ``over``, and the names
    of the kernels the traced program calls."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv(SWITCH, mode)
        traced = jax.jit(jax.value_and_grad(loss_of(
            case.spec(**over), case.tokens, True), has_aux=True)).trace(
                case.params)
    return traced.lower().compile()(case.params), set(
        _pallas_calls(traced.jaxpr.jaxpr))


def apart(got, want):
    """The distance of two trees over the second's norm."""
    leaves = jax.tree_util.tree_leaves
    norm = lambda trees: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(t)) for t in trees)))
    return norm([g - w for g, w in zip(leaves(got), leaves(want))]) / norm(
        leaves(want))


def distances(grads, wanted_grads):
    """({leaf: |g - w| / |w|}, the leaves the reference gives no
    gradient, each held to none in ``grads``)."""
    far, still = {}, []
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(wanted_grads)):
        name, norm = jax.tree_util.keystr(path), float(jnp.linalg.norm(w))
        if norm:
            far[name] = float(jnp.linalg.norm(g - w)) / norm
        else:
            assert not float(jnp.abs(g).max()), name
            still.append(name)
    return far, still


def check(case, mode, loss_tolerance, grad_tolerance, kernels=(), **over):
    """The product's loss within ``loss_tolerance`` of the reference's
    and every gradient leaf within ``grad_tolerance`` of its norm; the
    traced program calls every one of ``kernels`` (what a stack at its
    least depth for the interpreter has to reach: ROADMAP C16 (c)).
    Returns (the leaves' distances, the leaves without a gradient, the
    product's step statistics, what else the reference's ``loss`` gave)."""
    ((got, stats), grads), calls = product(case, mode, **over)
    assert calls >= set(kernels), set(kernels) - calls
    (want, rest), wanted_grads = wanted(case)
    assert abs(float(got) - float(want)) <= loss_tolerance * abs(
        float(want)), (float(got), float(want))
    far, still = distances(grads, wanted_grads)
    assert max(far.values()) <= grad_tolerance, sorted(
        far.items(), key=lambda item: -item[1])[:4]
    return far, still, stats, rest
