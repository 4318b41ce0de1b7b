"""The eager Tensor: a jax.Array wrapper with taped autograd.

Reference analogs: `phi::DenseTensor` (`/root/reference/paddle/phi/core/dense_tensor.h:37`)
for storage, `paddle::experimental::Tensor` (`paddle/phi/api/include/tensor.h`) for the
API object, and `AutogradMeta` (`paddle/fluid/eager/autograd_meta.h:61`) for the grad
slots.  Here all three collapse into one Python class over a `jax.Array` — the device
buffer, layout, and allocation are PJRT/XLA's business, not ours.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd import tape
from ..core import dtypes as _dt


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


class Tensor:
    """Eager tensor. `stop_gradient` defaults True (ref: VarBase default)."""

    __slots__ = (
        "_value",
        "stop_gradient",
        "_grad",
        "_node",
        "_out_index",
        "name",
        "persistable",
        "is_leaf_retain",
        "_grad_hooks",
        "sharding_spec",
        "process_mesh",
        "_st_sym",  # (program, sym_id) when produced under static capture
        "__weakref__",
    )

    def __init__(self, value, stop_gradient: bool = True, name: str | None = None):
        if isinstance(value, Tensor):
            value = value._value
        if not isinstance(value, (jax.Array, jax.core.Tracer)):
            value = jnp.asarray(value)
        self._value = value
        self.stop_gradient = stop_gradient
        self._grad = None
        self._node: tape.TapeNode | None = None
        self._out_index = 0
        self.name = name or ""
        self.persistable = False
        self.is_leaf_retain = False
        self._grad_hooks: list[Callable] = []
        self.sharding_spec = None  # logical PartitionSpec used by distributed train steps

    # ------------------------------------------------------------------ properties
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self):
        from ..core import device as _device

        try:
            devs = self._value.devices()
            dev = next(iter(devs))
            return _device.TPUPlace(dev.id) if dev.platform == "tpu" \
                else _device.CPUPlace(dev.id)
        except Exception:
            return _device._get_place()

    @property
    def grad(self):
        if self._grad is None:
            return None
        return Tensor(self._grad, stop_gradient=True)

    @grad.setter
    def grad(self, value):
        self._grad = _unwrap(value) if value is not None else None

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def T(self):
        from . import manipulation

        return manipulation.transpose(self, list(range(self.ndim))[::-1])

    # ------------------------------------------------------------------ numpy bridge
    def numpy(self):
        self._guard_static_inspect("numpy()")
        return np.asarray(self._value)

    def __array__(self, dtype=None):
        self._guard_static_inspect("np.asarray()")
        arr = np.asarray(self._value)
        return arr.astype(dtype) if dtype is not None else arr

    def item(self, *args):
        self._guard_static_inspect("item()")
        return self._value.item(*args)

    def tolist(self):
        return np.asarray(self._value).tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._value.shape[0]

    def __repr__(self):
        grad_str = "" if self.stop_gradient else ", stop_gradient=False"
        return (
            f"Tensor(shape={self.shape}, dtype={self._value.dtype}{grad_str},\n"
            f"       {np.array2string(np.asarray(jax.device_get(self._value)), prefix='       ')})"
        )

    def _guard_static_inspect(self, what):
        """Raise when build-time code inspects the VALUE of a symbolic tensor
        during static capture: builders execute on zero placeholders, so any
        Python branching on the value would silently bake in the zero branch.
        (The reference's static Variable cannot be value-inspected at all.)"""
        sym = getattr(self, "_st_sym", None)
        if sym is not None and _static_active_program is not None \
                and sym[0] is _static_active_program:
            raise RuntimeError(
                f"static capture: {what} called on a symbolic tensor during "
                "program build — its value here is a zero placeholder, not "
                "runtime data.  Use static.nn.cond/while_loop for "
                "value-dependent control flow, or fetch the value via "
                "Executor.run")

    def __bool__(self):
        self._guard_static_inspect("bool()")
        if self.size != 1:
            raise ValueError("truth value of multi-element Tensor is ambiguous")
        return bool(self._value)

    def __int__(self):
        self._guard_static_inspect("int()")
        return int(self._value)

    def __float__(self):
        self._guard_static_inspect("float()")
        return float(self._value)

    def __hash__(self):
        return id(self)

    def __format__(self, spec):
        if self.size == 1:
            return format(self.item(), spec)
        return repr(self)

    # ------------------------------------------------------------------ autograd
    def backward(self, grad_tensor=None, retain_graph: bool = False):
        tape.run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def _accumulate_grad(self, g):
        if g.dtype != self._value.dtype:
            g = g.astype(self._value.dtype)
        if self._grad is None:
            self._grad = g
        else:
            self._grad = self._grad + g

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero: bool = False):
        self._grad = jnp.zeros_like(self._value) if set_to_zero else None

    def zero_grad(self):
        self.clear_grad()

    def detach(self) -> "Tensor":
        t = Tensor(self._value, stop_gradient=True, name=self.name)
        sym = getattr(self, "_st_sym", None)
        if sym is not None:
            # detach is identity on the value: under static capture the
            # detached view keeps the symbolic identity (otherwise it would
            # be mis-classified as an external live leaf holding its
            # build-time placeholder value)
            t._st_sym = sym
        return t

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from . import math as _math

        return _math.assign(self)

    def register_hook(self, hook):
        """Grad hook (ref: varbase_patch_methods.py register_hook)."""

        def _h(g):
            r = hook(Tensor(g, stop_gradient=True))
            return g if r is None else _unwrap(r)

        self._grad_hooks.append(_h)
        handle = _HookHandle(self._grad_hooks, _h)
        return handle

    def retain_grads(self):
        self.is_leaf_retain = True
        self.stop_gradient = False

    # ------------------------------------------------------------------ mutation
    def set_value(self, value):
        """In-place value swap (rebind; the old autograd history is kept for grads
        already recorded — matches reference set_value semantics for parameters).

        Under static capture, setting a captured value records a program
        STATE WRITE (the analog of batch_norm's MeanOut in-graph output) and
        leaves the eager value untouched — the compiled step updates it."""
        if _static_state_write_hook is not None and isinstance(value, Tensor):
            if _static_state_write_hook(self, value):
                return self
        v = _unwrap(value)
        if not isinstance(v, (jax.Array, jax.core.Tracer)):
            v = jnp.asarray(v, dtype=self._value.dtype)
        if tuple(v.shape) != tuple(self._value.shape):
            raise ValueError(f"set_value shape mismatch {v.shape} vs {self._value.shape}")
        if v.dtype != self._value.dtype:
            v = v.astype(self._value.dtype)
        self._value = v
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def _rebind(self, v):
        """Internal: replace the underlying array AND clear tape history."""
        self._value = v
        self._node = None
        self._out_index = 0
        return self

    def _assume(self, other: "Tensor"):
        """Internal: become `other` INCLUDING its tape node — the in-place-op
        contract (relu_ etc. stay differentiable, unlike _rebind).

        The op that produced `other` recorded `self` among its tape inputs; if
        self simply adopted the new node, that recorded input would point at
        the node's own output (a self-loop) and the cotangent would be lost.
        So the recorded input is rewritten to a snapshot carrying self's OLD
        tape position (the reference's TensorWrapper/version-counter dance
        collapses to this under a functional tape)."""
        if other._node is not None:
            if self._node is None and not self.stop_gradient:
                raise RuntimeError(
                    "a leaf Tensor that requires grad is being used in an "
                    "in-place operation")
            snap = Tensor(self._value, stop_gradient=self.stop_gradient)
            snap._node = self._node
            snap._out_index = self._out_index
            # hooks belong to the VARIABLE, which now lives at the new tape
            # position — the snapshot edge must carry none or they fire twice
            snap._grad_hooks = []
            other._node.inputs = [snap if i is self else i
                                  for i in other._node.inputs]
            self._node = other._node
            self._out_index = other._out_index
            # the result of a differentiable op is differentiable, whatever
            # the old flag said (e.g. scatter_ into a constant with tracked
            # updates must pass gradients through)
            self.stop_gradient = False
        # op recorded no node (e.g. under no_grad): keep the existing history —
        # backward uses the tape's saved values, matching reference semantics
        self._value = other._value
        return self

    # value access used throughout the framework
    @property
    def value(self):
        return self._value

    def cpu(self):
        return Tensor(jax.device_get(self._value), stop_gradient=self.stop_gradient)

    def pin_memory(self):
        return self

    def cuda(self, *a, **k):
        return self

    def to(self, *args, **kwargs):
        # minimal parity: .to(dtype) / .to(device)
        for a in args:
            if isinstance(a, str) and a in ("cpu", "tpu", "gpu"):
                continue
            return self.astype(a)
        if "dtype" in kwargs:
            return self.astype(kwargs["dtype"])
        return self


class _HookHandle:
    def __init__(self, store, fn):
        self._store = store
        self._fn = fn

    def remove(self):
        try:
            self._store.remove(self._fn)
        except ValueError:
            pass


class Parameter(Tensor):
    """Trainable tensor (ref: python/paddle/fluid/framework.py Parameter).

    stop_gradient defaults False; `trainable` toggles it.
    """

    __slots__ = ("optimize_attr", "regularizer", "need_clip", "is_distributed",
                 "_asp_mask")

    def __init__(self, value, stop_gradient: bool | None = None, name: str | None = None, trainable=None):
        if trainable is not None:
            sg = not trainable
        elif stop_gradient is not None:
            sg = stop_gradient
        else:
            sg = False
        super().__init__(value, stop_gradient=sg, name=name)
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


# ----------------------------------------------------------------------------- op apply

# AMP autocast hook, registered by paddle_tpu.amp on import (avoids an import cycle).
_amp_cast_hook = None
# set by static.program._activate while a Program capture is live: records
# (pure_fn, tensor_args, raw_kwargs, outputs, name) onto the active Program
_static_capture_hook = None
# set alongside: set_value(captured) promotes buffer mutations to program
# state (BN running stats); the active program enables the value-inspection
# guard on placeholder-derived tensors
_static_state_write_hook = None
_static_active_program = None
_amp_state_ref = None


def _amp_enabled():
    return _amp_state_ref is not None and _amp_state_ref.get("enabled", False)


def apply_op(fn: Callable, args: tuple, kwargs: dict | None = None, name: str = "op", n_outputs: int | None = None):
    """The single dispatch point for every differentiable primitive op.

    Ref analog: the generated `*_dygraph_function` (eager_gen.py:271-295): run the
    kernel, then create a GradNode capturing inputs.  Here the "kernel" is a pure JAX
    function and the GradNode is the `jax.vjp` closure.
    `fn` receives raw arrays for every Tensor argument (positional only for
    differentiable ones).
    """
    kwargs = kwargs or {}
    raw_args = [_unwrap(a) for a in args]
    raw_kwargs = {k: _unwrap(v) for k, v in kwargs.items()}

    if _amp_cast_hook is not None and _amp_enabled():
        inner = fn
        fn = lambda *a, **k: inner(*_amp_cast_hook(name, list(a)), **k)

    diff_idx = [
        i
        for i, a in enumerate(args)
        if isinstance(a, Tensor)
        and not a.stop_gradient
        and _dt.is_differentiable(a._value.dtype)
    ]

    if not tape.is_grad_enabled() or not diff_idx:
        out = fn(*raw_args, **raw_kwargs)
        res = _wrap_outputs(out, None, name)
        if _static_capture_hook is not None:
            _static_capture_hook(fn, args, raw_kwargs, res, name)
        return res

    def closed(*diff_arrays):
        full = list(raw_args)
        for i, arr in zip(diff_idx, diff_arrays):
            full[i] = arr
        return fn(*full, **raw_kwargs)

    out, vjp_fn = jax.vjp(closed, *[raw_args[i] for i in diff_idx])
    node_inputs = [args[i] for i in diff_idx]
    is_tuple = isinstance(out, (tuple, list))
    outs_flat = out if is_tuple else (out,)
    out_avals = [(o.shape, o.dtype) for o in outs_flat]
    node = tape.TapeNode(vjp_fn, node_inputs, out_avals, name=name, out_is_tuple=is_tuple,
                         primal_fn=closed)
    res = _wrap_outputs(out, node, name)
    if _static_capture_hook is not None:
        _static_capture_hook(fn, args, raw_kwargs, res, name)
    return res


def _host_nan_check(name, arr):
    if not np.all(np.isfinite(arr)):
        raise RuntimeError(
            f"Operator '{name}' output contains Inf or NaN "
            f"(FLAGS_check_nan_inf is on; ref framework/details/nan_inf_utils.h:29)")


def _check_nan_inf(name, out):
    """Per-op NaN/Inf debug mode (ref FLAGS_check_nan_inf + nan_inf_utils.h:29:
    CheckVarHasNanOrInf after every op).  Eager values are checked inline;
    traced values get a host callback so the check also fires inside jit."""
    from ..framework import flags as _flags

    if not _flags.get_flag("FLAGS_check_nan_inf", False):
        return
    for o in out if isinstance(out, (tuple, list)) else (out,):
        if hasattr(o, "dtype") and _dt.is_floating(o.dtype):
            if isinstance(o, jax.core.Tracer):
                jax.debug.callback(_host_nan_check, name, o)
            else:
                _host_nan_check(name, np.asarray(o))


def _wrap_outputs(out, node, name):
    _check_nan_inf(name, out)
    if isinstance(out, (tuple, list)):
        wrapped = []
        for i, o in enumerate(out):
            t = Tensor(o, stop_gradient=node is None)
            t._node = node
            t._out_index = i
            wrapped.append(t)
        return tuple(wrapped)
    t = Tensor(out, stop_gradient=node is None)
    t._node = node
    return t


def defop(name: str, fn: Callable):
    """Declaratively produce a user-facing op from a pure-JAX impl.

    This replaces the reference's YAML->C++ codegen pipeline
    (`paddle/phi/api/yaml/generator/api_gen.py`): the op table IS the API.
    """

    def op(*args, **kwargs):
        return apply_op(fn, args, kwargs, name=name)

    op.__name__ = name
    op.__qualname__ = name
    op.raw = fn
    return op
