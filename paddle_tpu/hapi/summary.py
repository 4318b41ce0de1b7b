"""model summary / flops (ref: python/paddle/hapi/model_summary.py, hapi/dynamic_flops.py)."""
from __future__ import annotations

import numpy as np

from ..nn.layer.layers import Layer
from ..tensor.tensor import Tensor
from ..tensor import creation


def summary(net: Layer, input_size=None, dtypes=None, input=None):
    rows = []
    hooks = []

    def register(layer):
        def hook(l, inputs, outputs):
            n_params = sum(int(np.prod(p.shape)) for p in l._parameters.values() if p is not None)
            out_shape = outputs.shape if isinstance(outputs, Tensor) else "-"
            rows.append((type(l).__name__, str(out_shape), n_params))

        if not layer._sub_layers:
            hooks.append(layer.register_forward_post_hook(hook))

    net.apply(register)
    try:
        if input is None and input_size is not None:
            sizes = [input_size] if isinstance(input_size, tuple) else input_size
            if isinstance(input_size, tuple) and input_size and isinstance(input_size[0], int):
                sizes = [input_size]
            inputs = [creation.zeros([s if s is not None else 1 for s in sz],
                                     (dtypes[i] if isinstance(dtypes, (list, tuple)) else dtypes) or "float32")
                      for i, sz in enumerate(sizes)]
            was_training = net.training
            net.eval()
            net(*inputs)
            if was_training:
                net.train()
    finally:
        for h in hooks:
            h.remove()

    total = sum(int(np.prod(p.shape)) for p in net.parameters())
    trainable = sum(int(np.prod(p.shape)) for p in net.parameters() if not p.stop_gradient)
    lines = ["-" * 70, f"{'Layer':<28}{'Output Shape':<28}{'Param #':<12}", "=" * 70]
    for name, shape, n in rows:
        lines.append(f"{name:<28}{shape:<28}{n:<12}")
    lines += ["=" * 70, f"Total params: {total:,}", f"Trainable params: {trainable:,}",
              f"Non-trainable params: {total - trainable:,}", "-" * 70]
    print("\n".join(lines))
    return {"total_params": total, "trainable_params": trainable}


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Rough analytic flops via XLA cost analysis when available."""
    import jax

    try:
        x = np.zeros(input_size, np.float32)
        params, buffers = net.functional_state()

        def f(params, buffers, x):
            restore = net.bind_functional_state(params, buffers)
            try:
                out = net(Tensor(x))
            finally:
                restore()
            return out._value if isinstance(out, Tensor) else out

        lowered = jax.jit(f).lower(params, buffers, x)
        return int(lowered.compile().cost_analysis().get("flops", 0))
    except Exception:
        return 0
