"""paddle.signal namespace (reference: python/paddle/signal.py): stft/istft."""
from __future__ import annotations

import jax.numpy as jnp

from .core.apply import apply
from .core.tensor import Tensor


def frame(x, frame_length, hop_length, axis=-1, name=None):
    """Slice x into overlapping frames along `axis` (reference: signal.frame)."""

    def fn(v):
        if axis not in (-1, v.ndim - 1):
            raise NotImplementedError("frame currently supports the last axis")
        n = v.shape[-1]
        num = 1 + (n - frame_length) // hop_length
        idx = jnp.arange(frame_length)[None, :] + hop_length * jnp.arange(num)[:, None]
        out = v[..., idx]  # [..., num, frame_length]
        return jnp.swapaxes(out, -1, -2)  # [..., frame_length, num]

    return apply("frame", fn, x)


def overlap_add(x, hop_length, axis=-1, name=None):
    def fn(v):
        # v: [..., frame_length, num_frames]
        fl, num = v.shape[-2], v.shape[-1]
        n = fl + hop_length * (num - 1)
        out = jnp.zeros(v.shape[:-2] + (n,), v.dtype)
        for i in range(num):  # static small loop; XLA unrolls
            out = out.at[..., i * hop_length : i * hop_length + fl].add(v[..., :, i])
        return out

    return apply("overlap_add", fn, x)


def stft(
    x,
    n_fft,
    hop_length=None,
    win_length=None,
    window=None,
    center=True,
    pad_mode="reflect",
    normalized=False,
    onesided=True,
    name=None,
):
    hop_length = hop_length or n_fft // 4
    win_length = win_length or n_fft
    win_v = window._value if isinstance(window, Tensor) else (jnp.ones(win_length) if window is None else jnp.asarray(window))
    if win_length < n_fft:  # center-pad window to n_fft (reference behavior)
        lp = (n_fft - win_length) // 2
        win_v = jnp.pad(win_v, (lp, n_fft - win_length - lp))

    def fn(v):
        if center:
            pad = n_fft // 2
            v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(pad, pad)], mode=pad_mode)
        n = v.shape[-1]
        num = 1 + (n - n_fft) // hop_length
        idx = jnp.arange(n_fft)[None, :] + hop_length * jnp.arange(num)[:, None]
        # window in the INPUT dtype: the default jnp.ones window is f64
        # under the global x64 mode, and f32*f64 would promote the whole
        # transform to complex128 (reference: float32 in -> complex64 out)
        frames = v[..., idx] * win_v.astype(v.dtype)  # [..., num, n_fft]
        spec = jnp.fft.rfft(frames, axis=-1) if onesided else jnp.fft.fft(frames, axis=-1)
        if normalized:
            spec = spec / jnp.sqrt(jnp.asarray(n_fft, spec.real.dtype))
        return jnp.swapaxes(spec, -1, -2)  # [..., freq, num_frames]

    return apply("stft", fn, x)


def istft(
    x,
    n_fft,
    hop_length=None,
    win_length=None,
    window=None,
    center=True,
    normalized=False,
    onesided=True,
    length=None,
    return_complex=False,
    name=None,
):
    hop_length = hop_length or n_fft // 4
    win_length = win_length or n_fft
    win_v = window._value if isinstance(window, Tensor) else (jnp.ones(win_length) if window is None else jnp.asarray(window))
    if win_length < n_fft:
        lp = (n_fft - win_length) // 2
        win_v = jnp.pad(win_v, (lp, n_fft - win_length - lp))

    def fn(v):
        spec = jnp.swapaxes(v, -1, -2)  # [..., num_frames, freq]
        if normalized:
            spec = spec * jnp.sqrt(jnp.asarray(n_fft, spec.real.dtype))
        frames = jnp.fft.irfft(spec, n=n_fft, axis=-1) if onesided else jnp.fft.ifft(spec, axis=-1).real
        # window in the frames' real dtype (complex64 in -> float32 out;
        # see stft: the default window is f64 under global x64)
        win = win_v.astype(frames.dtype)
        frames = frames * win
        num = frames.shape[-2]
        n = n_fft + hop_length * (num - 1)
        out = jnp.zeros(frames.shape[:-2] + (n,), frames.dtype)
        wsum = jnp.zeros((n,), frames.dtype)
        for i in range(num):
            out = out.at[..., i * hop_length : i * hop_length + n_fft].add(frames[..., i, :])
            wsum = wsum.at[i * hop_length : i * hop_length + n_fft].add(win**2)
        out = out / jnp.maximum(wsum, 1e-10)
        if center:
            pad = n_fft // 2
            out = out[..., pad : n - pad]
        if length is not None:
            out = out[..., :length]
        return out

    return apply("istft", fn, x)
