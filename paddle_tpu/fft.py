"""paddle.fft namespace (reference: python/paddle/fft.py) over jnp.fft.

Every op is jnp.fft (XLA lax.fft) on the default backend — cpu, gpu and tpu
all lower it, complex results and grad-through-fft included. Norm semantics
match the reference ("backward"/"ortho"/"forward").
"""
from __future__ import annotations

import jax.numpy as jnp

from .core.apply import apply
from .core.tensor import Tensor


def _mk1(jfn, name):
    def op(x, n=None, axis=-1, norm="backward", name_arg=None):
        return apply(name, lambda v: jfn(v, n=n, axis=axis, norm=norm), x)

    op.__name__ = name
    return op


def _mkn(jfn, name, default_axes=None):
    def op(x, s=None, axes=default_axes, norm="backward", name_arg=None):
        return apply(name, lambda v: jfn(v, s=s, axes=axes, norm=norm), x)

    op.__name__ = name
    return op


fft = _mk1(jnp.fft.fft, "fft")
ifft = _mk1(jnp.fft.ifft, "ifft")
rfft = _mk1(jnp.fft.rfft, "rfft")
irfft = _mk1(jnp.fft.irfft, "irfft")
hfft = _mk1(jnp.fft.hfft, "hfft")
ihfft = _mk1(jnp.fft.ihfft, "ihfft")
fft2 = _mkn(jnp.fft.fft2, "fft2", default_axes=(-2, -1))
ifft2 = _mkn(jnp.fft.ifft2, "ifft2", default_axes=(-2, -1))
rfft2 = _mkn(jnp.fft.rfft2, "rfft2", default_axes=(-2, -1))
irfft2 = _mkn(jnp.fft.irfft2, "irfft2", default_axes=(-2, -1))
fftn = _mkn(jnp.fft.fftn, "fftn")
ifftn = _mkn(jnp.fft.ifftn, "ifftn")
rfftn = _mkn(jnp.fft.rfftn, "rfftn")
irfftn = _mkn(jnp.fft.irfftn, "irfftn")


def fftfreq(n, d=1.0, dtype=None, name=None) -> Tensor:
    return Tensor(jnp.fft.fftfreq(n, d).astype(dtype or jnp.float32))


def rfftfreq(n, d=1.0, dtype=None, name=None) -> Tensor:
    return Tensor(jnp.fft.rfftfreq(n, d).astype(dtype or jnp.float32))


def fftshift(x, axes=None, name=None):
    return apply("fftshift", lambda v: jnp.fft.fftshift(v, axes=axes), x)


def ifftshift(x, axes=None, name=None):
    return apply("ifftshift", lambda v: jnp.fft.ifftshift(v, axes=axes), x)


def hfftn(x, s=None, axes=None, norm="backward", name=None):
    """n-D FFT of a Hermitian-symmetric input -> real output (reference
    fft.py hfftn). Composed as a complex FFT over the leading axes + a 1-D
    hfft over the last: per-stage norm factors multiply to the full-size
    factor for backward/forward/ortho alike."""
    axes = tuple(axes) if axes is not None else tuple(range(-len(s), 0)) if s is not None else tuple(range(-x.ndim, 0))
    lead, last = axes[:-1], axes[-1]
    s_lead = list(s[:-1]) if s is not None else None
    n_last = s[-1] if s is not None else None
    out = x
    if lead:
        out = fftn(out, s=s_lead, axes=lead, norm=norm)
    return hfft(out, n=n_last, axis=last, norm=norm)


def ihfftn(x, s=None, axes=None, norm="backward", name=None):
    """Inverse of hfftn: 1-D ihfft over the last axis + inverse complex FFT
    over the leading axes (reference fft.py ihfftn)."""
    axes = tuple(axes) if axes is not None else tuple(range(-len(s), 0)) if s is not None else tuple(range(-x.ndim, 0))
    lead, last = axes[:-1], axes[-1]
    s_lead = list(s[:-1]) if s is not None else None
    n_last = s[-1] if s is not None else None
    out = ihfft(x, n=n_last, axis=last, norm=norm)
    if lead:
        out = ifftn(out, s=s_lead, axes=lead, norm=norm)
    return out


def hfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    """2-D Hermitian FFT (reference fft.py hfft2)."""
    return hfftn(x, s=s, axes=axes, norm=norm)


def ihfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    """2-D inverse Hermitian FFT (reference fft.py ihfft2)."""
    return ihfftn(x, s=s, axes=axes, norm=norm)
