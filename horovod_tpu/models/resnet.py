"""ResNet v1.5 family in Flax — the benchmark workload.

The reference has no model code of its own; its synthetic benchmark pulls
ResNet-50 from Keras applications (examples/tensorflow_synthetic_benchmark.
py:24-42) and the docs' scaling numbers are ResNet-101/Inception V3/VGG-16
(docs/benchmarks.md:5-6). This is the TPU-native equivalent model zoo for
those benchmarks.

TPU-first choices: bf16 activations (MXU-native) with fp32 parameters and
fp32 batch-norm statistics; NHWC layout (XLA's preferred conv layout on
TPU); no data-dependent control flow, so the whole step jits into one
program.

Batch-norm activations are bf16 end to end: flax computes the mean/var
reductions in float32 internally regardless of ``dtype``
(``flax.linen.normalization._compute_stats`` forces float32 reductions), so
only the normalized *output* is bf16. The backward pass of ResNet-50 on TPU
is HBM-bandwidth-bound on exactly these BN input/output tensors (profiled:
the top device fusions are BN-backward reduces), and keeping them bf16
rather than fp32 halves that traffic — measured +22% train-step throughput
on a v5e with no change to the fp32 statistics.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..ops import fused_bn

ModuleDef = Any


class FusedBNAct(nn.Module):
    """Train/eval batch-norm with the residual add and ReLU fused into
    the op (ops/fused_bn.py) — a hand-written 2+3-pass custom VJP
    instead of flax autodiff's graph. Parameter/stat layout matches
    ``nn.BatchNorm`` ('scale'/'bias' params, batch_stats 'mean'/'var',
    biased fp32 moments, same momentum update), so checkpoints are
    interchangeable with the unfused model."""

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    relu: bool = True
    scale_init: Callable = nn.initializers.ones
    impl: str = "auto"  # fused_bn.bn_act impls; 'auto' measured fastest

    @nn.compact
    def __call__(self, x, residual=None):
        c = x.shape[-1]
        gamma = self.param("scale", self.scale_init, (c,), jnp.float32)
        beta = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda s: jnp.zeros(s, jnp.float32), (c,))
        ra_var = self.variable(
            "batch_stats", "var",
            lambda s: jnp.ones(s, jnp.float32), (c,))
        if self.use_running_average:
            return fused_bn.bn_act_inference(
                x, gamma, beta, ra_mean.value, ra_var.value,
                residual=residual, eps=self.epsilon, relu=self.relu)
        y, mean, var = fused_bn.bn_act(
            x, gamma, beta, residual=residual, eps=self.epsilon,
            relu=self.relu, impl=self.impl)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
            ra_var.value = m * ra_var.value + (1.0 - m) * var
        return y


class BottleneckBlock(nn.Module):
    """ResNet v1.5 bottleneck (stride in the 3x3, torchvision-style).

    With ``fused_norm`` set (a FusedBNAct partial), each bn+relu pair is
    one fused op and the block's residual join (bn3 + add + relu) is a
    single bn_act with the residual fused in — same parameter tree as
    the flax path."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu
    fused_norm: Optional[ModuleDef] = None

    @nn.compact
    def __call__(self, x):
        residual = x
        fused = self.fused_norm
        if fused is not None and self.act is not nn.relu:
            # The fused op hardcodes ReLU; honoring a custom activation
            # silently with ReLU instead would make the two impls
            # (documented as computing the same function) diverge.
            raise ValueError(
                "fused_norm supports act=nn.relu only; use the flax "
                "norm path (bn_impl='flax') with a custom activation")
        y = self.conv(self.filters, (1, 1), use_bias=False, name="conv1")(x)
        if fused is not None:
            y = fused(name="bn1")(y)
        else:
            y = self.act(self.norm(name="bn1")(y))
        y = self.conv(self.filters, (3, 3), self.strides, use_bias=False,
                      name="conv2")(y)
        if fused is not None:
            y = fused(name="bn2")(y)
        else:
            y = self.act(self.norm(name="bn2")(y))
        y = self.conv(self.filters * 4, (1, 1), use_bias=False,
                      name="conv3")(y)

        if residual.shape[-1] != self.filters * 4 or self.strides != (1, 1):
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 use_bias=False, name="downsample_conv")(
                residual)
            if fused is not None:
                residual = fused(relu=False, name="downsample_bn")(residual)
            else:
                residual = self.norm(name="downsample_bn")(residual)
        if fused is not None:
            return fused(scale_init=nn.initializers.zeros,
                         name="bn3")(y, residual=residual)
        y = self.norm(scale_init=nn.initializers.zeros, name="bn3")(y)
        return self.act(residual + y)


class ResNet(nn.Module):
    """ResNet v1.5 with bf16 compute / fp32 params.

    ``bn_impl`` selects the batch-norm implementation: 'flax' (default)
    is plain ``nn.BatchNorm`` + separate relu/add; anything else routes
    through the fused bn(+residual)(+relu) custom-VJP op
    (ops/fused_bn.py) with that string as its impl
    ('auto'/'jnp'/'pallas'/'interpret'). Both paths share one parameter
    tree. 'flax' is the default because it MEASURES fastest end to end
    on v5e (full train step, in-process A/B, round 4:
    flax 2312 img/s vs hand-structured jnp VJP 1586 vs Pallas kernels
    1002): XLA's whole-graph fusion of the autodiff backward beats
    locally pass-optimal but fusion-opaque custom ops — see
    docs/benchmarks.md for the full measurement ladder.

    ``bn_axis_name`` enables distributed batch norm
    (docs/data.md#sync-bn): batch statistics psum'd across the named
    mesh axis — the large-batch technique of arXiv 1909.09756 — with
    the same parameter/stat tree as the local paths. Requires the
    model to run inside ``shard_map``/``pmap`` over that axis, and
    ``bn_impl='flax'`` (the fused custom-VJP op computes its stats
    internally)."""

    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    bn_impl: str = "flax"
    bn_axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, dtype=self.dtype)
        if self.bn_axis_name is not None:
            if self.bn_impl != "flax":
                raise ValueError(
                    "bn_axis_name (distributed batch norm) requires "
                    "bn_impl='flax': the fused bn op computes its "
                    "statistics inside its custom VJP and cannot psum "
                    "them (docs/data.md#sync-bn)")
            from ..data.sync_bn import SyncBatchNorm
            norm = partial(SyncBatchNorm, use_running_average=not train,
                           axis_name=self.bn_axis_name, momentum=0.9,
                           epsilon=1e-5, dtype=self.dtype)
        else:
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           axis_name=None)
        fused = None
        if self.bn_impl != "flax":
            fused = partial(FusedBNAct, use_running_average=not train,
                            momentum=0.9, epsilon=1e-5,
                            impl=self.bn_impl)

        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                 use_bias=False, name="conv_init")(x)
        if fused is not None:
            x = fused(name="bn_init")(x)
        else:
            x = nn.relu(norm(name="bn_init")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(self.num_filters * 2 ** i,
                                    strides=strides, conv=conv, norm=norm,
                                    fused_norm=fused,
                                    name=f"stage{i + 1}_block{j + 1}")(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])
