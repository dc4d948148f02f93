"""Tensor: a Paddle-shaped eager tensor over jax.Array.

Reference parity: phi::DenseTensor + the eager Tensor exposed via
paddle/fluid/pybind/eager.cc and the ~2000 methods of python/paddle/tensor/.
Upstream-canonical paths, unverified (SURVEY.md §0).

Design: `Tensor` owns a jax.Array (`_data`) plus autograd metadata
(stop_gradient, grad, producing GradNode). All computation delegates to the op
surface in paddle_tpu.ops, which records the tape (autograd/tape.py). Method
attachment happens in paddle_tpu/ops/__init__ so the op table is the single
source of truth (the reference generates these bindings from ops.yaml —
SURVEY.md §2.1 codegen row; our "codegen" is runtime attachment).

In-place ops rebind `_data` and bump `_version` — functional JAX has no
aliasing, so in-place is copy-on-write by construction (SURVEY.md §7 hard
part #1): cheap under XLA because donation/fusion removes the copies in jitted
code.
"""
from __future__ import annotations

from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from .device import Place, _default_place


class Tensor:
    __slots__ = (
        "_data", "stop_gradient", "grad", "_grad_node", "_out_index",
        "_retain_grads", "_hooks", "name", "persistable", "_version",
        "trainable", "__weakref__", "__dict__",
    )

    _next_id = 0

    def __init__(self, data, stop_gradient: bool = True, name: Optional[str] = None):
        if isinstance(data, Tensor):
            data = data._data
        self._data = data if isinstance(data, jax.Array) else jnp.asarray(data)
        self.stop_gradient = stop_gradient
        self.grad: Optional[Tensor] = None
        self._grad_node = None
        self._out_index = 0
        self._retain_grads = False
        self._hooks = []
        if name is None:
            name = f"generated_tensor_{Tensor._next_id}"
            Tensor._next_id += 1
        self.name = name
        self.persistable = False
        self.trainable = not stop_gradient
        self._version = 0

    # ---- basic properties -------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._data.dtype)

    @property
    def ndim(self) -> int:
        return self._data.ndim

    ndimension = ndim
    rank = ndim

    @property
    def size(self) -> int:
        return int(self._data.size)

    @property
    def itemsize(self) -> int:
        return self._data.dtype.itemsize

    @property
    def nbytes(self) -> int:
        return self.size * self.itemsize

    @property
    def place(self) -> Place:
        # a concrete jax.Array knows its devices; a tracer (inside
        # to_static/jit) has no placement yet and reports the default
        if isinstance(self._data, jax.Array) and \
                not isinstance(self._data, jax.core.Tracer):
            return Place(next(iter(self._data.devices())))
        return _default_place()

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    @property
    def T(self) -> "Tensor":
        from .. import ops
        return ops.transpose(self, list(range(self.ndim))[::-1])

    @property
    def mT(self) -> "Tensor":
        from .. import ops
        perm = list(range(self.ndim))
        perm[-2], perm[-1] = perm[-1], perm[-2]
        return ops.transpose(self, perm)

    @property
    def mH(self) -> "Tensor":
        """Conjugate matrix transpose (upstream Tensor.mH — VERDICT r4
        missing 4): conj() with the last two dims swapped."""
        from .. import ops
        perm = list(range(self.ndim))
        perm[-2], perm[-1] = perm[-1], perm[-2]
        return ops.transpose(ops.conj(self), perm)

    @property
    def real(self) -> "Tensor":
        from .. import ops
        return ops.real(self)

    @property
    def imag(self) -> "Tensor":
        from .. import ops
        return ops.imag(self)

    # ---- conversion -------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args) -> Any:
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def astype(self, dt) -> "Tensor":
        from .. import ops
        return ops.cast(self, dt)

    cast = astype

    def clone(self) -> "Tensor":
        from .. import ops
        return ops.assign(self)

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True, name=self.name)
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        return self

    def numel(self) -> int:
        return self.size

    def element_size(self) -> int:
        return self.itemsize

    def cpu(self) -> "Tensor":
        return Tensor(jax.device_put(self._data, jax.devices("cpu")[0]),
                      stop_gradient=self.stop_gradient)

    def to(self, *args, **kwargs) -> "Tensor":
        """tensor.to(device) / to(dtype) / to(device, dtype)."""
        from .device import set_device
        dev, dt = None, None
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (str, Place)):
                if isinstance(a, str) and a in dtypes._ALIASES:
                    dt = a
                else:
                    dev = a
            else:
                dt = a
        out = self
        if dt is not None:
            out = out.astype(dt)
        if dev is not None:
            place = dev if isinstance(dev, Place) else set_device(dev)
            out = Tensor(jax.device_put(out._data, place.jax_device),
                         stop_gradient=out.stop_gradient)
        return out

    def pin_memory(self) -> "Tensor":
        return self  # host staging is owned by the io pipeline on TPU

    def contiguous(self) -> "Tensor":
        return self  # jax.Array layout is compiler-owned

    def is_contiguous(self) -> bool:
        return True

    # ---- autograd surface -------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False) -> None:
        from ..autograd import tape
        tape.backward(self, grad_tensor, retain_graph=retain_graph)

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Handle:
            def remove(handle_self):
                if hook in self._hooks:
                    self._hooks.remove(hook)

        return _Handle()

    def retain_grads(self) -> None:
        self._retain_grads = True

    def clear_grad(self) -> None:
        self.grad = None

    clear_gradient = clear_grad

    @property
    def gradient(self):
        return None if self.grad is None else self.grad.numpy()

    # ---- python protocol --------------------------------------------------
    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self) -> bool:
        return bool(self.numpy())

    def __int__(self) -> int:
        return int(self.numpy())

    def __float__(self) -> float:
        return float(self.numpy())

    def __index__(self) -> int:
        return int(self.numpy())

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return format(str(self), spec)

    def __repr__(self) -> str:
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"place={self.place}{grad_info},\n       {self.numpy()})")

    def __hash__(self):
        return id(self)

    def __getitem__(self, idx) -> "Tensor":
        from .. import ops
        return ops.getitem(self, idx)

    def __setitem__(self, idx, value) -> None:
        from .. import ops
        ops.setitem_(self, idx, value)

    # ---- in-place helpers -------------------------------------------------
    def _rebind(self, new_data) -> "Tensor":
        self._data = new_data
        self._version += 1
        return self

    def set_value(self, value) -> "Tensor":
        v = value._data if isinstance(value, Tensor) else jnp.asarray(value)
        if tuple(v.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {v.shape} vs {self._data.shape}")
        return self._rebind(v.astype(self._data.dtype))

    def copy_(self, other, blocking: bool = True) -> "Tensor":
        return self.set_value(other)

    def zero_(self) -> "Tensor":
        return self._rebind(jnp.zeros_like(self._data))

    def fill_(self, value) -> "Tensor":
        return self._rebind(jnp.full_like(self._data, value))

    # arithmetic dunders are attached by paddle_tpu.ops (single source of
    # truth for op definitions — see ops/__init__.py _attach_tensor_methods)

    # jax pytree protocol: Tensors flatten to their arrays so jitted
    # functions can take/return Tensors directly.


def _tensor_flatten(t: Tensor):
    return (t._data,), (t.stop_gradient,)


def _tensor_unflatten(aux, children):
    return Tensor(children[0], stop_gradient=aux[0])


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)


class Parameter(Tensor):
    """Trainable tensor — paddle.base.framework.EagerParamBase parity."""

    __slots__ = ("optimize_attr", "regularizer", "do_model_average", "need_clip", "is_distributed")

    def __init__(self, data, name: Optional[str] = None, trainable: bool = True):
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.do_model_average = None
        self.need_clip = True
        self.is_distributed = False

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


jax.tree_util.register_pytree_node(
    Parameter,
    lambda p: ((p._data,), (p.stop_gradient,)),
    lambda aux, ch: Parameter(ch[0], trainable=not aux[0]),
)


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor parity: python scalars → float32/int64 defaults."""
    if isinstance(data, Tensor):
        arr = data._data
        if dtype is not None:
            arr = arr.astype(dtypes.convert_dtype(dtype))
        t = Tensor(arr, stop_gradient=stop_gradient)
        return t
    if isinstance(data, jax.Array):
        # keep the array (and its sharding) as-is — round-tripping through
        # numpy would gather a sharded array onto one device
        arr = data if dtype is None else \
            data.astype(dtypes.convert_dtype(dtype))
        return Tensor(arr, stop_gradient=stop_gradient)
    if dtype is not None:
        arr = jnp.asarray(data, dtype=dtypes.convert_dtype(dtype))
    else:
        npv = np.asarray(data)
        if npv.dtype == np.float64 and not isinstance(data, np.ndarray):
            # python floats / float lists default to the paddle default dtype
            arr = jnp.asarray(npv, dtype=dtypes.get_default_dtype())
        else:
            arr = jnp.asarray(npv)
    if place is not None:
        p = place if isinstance(place, Place) else None
        if p is None:
            from .device import set_device
            p = set_device(place)
        arr = jax.device_put(arr, p.jax_device)
    return Tensor(arr, stop_gradient=stop_gradient)
