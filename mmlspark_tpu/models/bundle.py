"""ModelBundle: a serializable (architecture + weights) unit.

Replaces the reference's `SerializableFunction` wrapper around CNTK.Function
(com/microsoft/CNTK/SerializableFunction.scala:85-143): a model is
(builder name + kwargs) — reconstructable code — plus a weights pytree,
picklable because weights are stored as numpy.  Named outputs ("taps") give
CNTK-style node addressing for feed/fetch dicts (CNTKModel.scala:229-371).
"""
from __future__ import annotations

import uuid
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ModelBundle", "FlaxBundle", "FunctionBundle", "register_builder",
           "get_builder"]

# name -> (module factory, layer names) — grows as model families are added
_BUILDERS: Dict[str, Callable[..., Any]] = {}


def register_builder(name: str, factory: Callable[..., Any]):
    _BUILDERS[name] = factory
    return factory


def get_builder(name: str) -> Callable[..., Any]:
    """Look up a registered model builder by name; ValueError lists the
    registry on a miss (the public face of the zoo registry)."""
    try:
        return _BUILDERS[name]
    except KeyError:
        raise ValueError(
            f"unknown model builder {name!r}; registered: "
            f"{sorted(_BUILDERS)}") from None


def _to_numpy(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


class ModelBundle:
    """Interface: named-output model with weights.

    `bundle_id` is a stable identity for executor caching: unique per
    construction, preserved through pickle (same weights -> same id), unlike
    `id()` which CPython recycles.
    """

    input_shape: Optional[Tuple[int, ...]] = None  # per-example, e.g. (224,224,3)
    layer_names: List[str] = []

    def __new__(cls, *args, **kwargs):
        obj = super().__new__(cls)
        obj.bundle_id = uuid.uuid4().hex
        return obj

    def apply(self, variables, batch: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    @property
    def variables(self):
        raise NotImplementedError


class FlaxBundle(ModelBundle):
    """A registered flax module + its variables."""

    def __init__(
        self,
        builder: str,
        builder_kwargs: Optional[dict] = None,
        variables: Any = None,
        input_shape: Optional[Sequence[int]] = None,
        layer_names: Optional[List[str]] = None,
        seed: int = 0,
    ):
        self.builder = builder
        self.builder_kwargs = dict(builder_kwargs or {})
        self.input_shape = tuple(input_shape) if input_shape else None
        self._module = None
        if variables is None:
            if self.input_shape is None:
                raise ValueError("need input_shape to initialize variables")
            # token models (nn.Embed inputs) declare input_dtype=int32 on
            # the module; image/feature models default to float32
            in_dtype = getattr(self.module, "input_dtype", jnp.float32)
            # ONE compiled program, not an eager op (and a compile) per
            # initializer: a ResNet-50's worth of those is seconds on the
            # CPU backend and far worse on a chip
            variables = jax.jit(self.module.init)(
                {"params": jax.random.PRNGKey(seed)},
                jnp.zeros((1, *self.input_shape), in_dtype),
            )
            # drop the transformer's init-time sown K/V (a per-call
            # intermediate, not weights); caller-supplied variables pass
            # through untouched — their collections are their business
            variables = {c: v for c, v in dict(variables).items()
                         if c != "kvcache"}
        self._variables = _to_numpy(variables)
        if layer_names is None:
            layer_names = getattr(self.module, "layer_names", None) or self._infer_layer_names()
        self.layer_names = list(layer_names)

    def _infer_layer_names(self) -> List[str]:
        from .resnet import LAYER_NAMES, ResNet

        if isinstance(self.module, ResNet):
            return list(LAYER_NAMES)
        return []

    @property
    def module(self):
        if self._module is None:
            self._module = get_builder(self.builder)(**self.builder_kwargs)
        return self._module

    @property
    def variables(self):
        return self._variables

    @variables.setter
    def variables(self, v):
        self._variables = _to_numpy(v)

    def apply(self, variables, batch: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        out = self.module.apply(variables, batch, train=False)
        if isinstance(out, tuple) and len(out) == 2 and isinstance(out[1], dict):
            _, taps = out
            return taps
        if isinstance(out, dict):
            return out
        return {"output": out}

    # pickle support: drop the live module (rebuilt lazily)
    def __getstate__(self):
        d = dict(self.__dict__)
        d["_module"] = None
        return d


class FunctionBundle(ModelBundle):
    """Arbitrary picklable `fn(variables, batch) -> dict|array` — the escape
    hatch matching CNTKModel's arbitrary-graph generality."""

    def __init__(self, fn, variables=None, input_shape=None, layer_names=None):
        self.fn = fn
        self._variables = _to_numpy(variables) if variables is not None else {}
        self.input_shape = tuple(input_shape) if input_shape else None
        self.layer_names = list(layer_names or ["output"])

    @property
    def variables(self):
        return self._variables

    def apply(self, variables, batch):
        out = self.fn(variables, batch)
        return out if isinstance(out, dict) else {"output": out}


# register the vision zoo (resnets + classic CNNs)
def _register_defaults():
    from . import convnets as C
    from . import resnet as R

    for name in ("resnet18", "resnet34", "resnet50", "resnet101", "resnet152"):
        register_builder(name, getattr(R, name))
    for name in ("alexnet", "vgg11", "vgg16", "convnet_cifar"):
        register_builder(name, getattr(C, name))
    from .transformer import transformer_lm

    register_builder("transformer_lm", transformer_lm)
    from . import vit as V

    for name in ("vit_tiny", "vit_small", "vit_base"):
        register_builder(name, getattr(V, name))


_register_defaults()
