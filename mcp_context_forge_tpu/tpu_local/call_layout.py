"""The one array a dispatch hands its step program.

Everything the host builds for a call — the rows' tokens, positions, budgets,
stop ids, sampling parameters, the dispatch counter the step key is folded
from — is per row, so a call is ONE int32 buffer ``[rows, width]``: a field is
a run of columns, a float32 field rides bit for bit (a view on the host,
``lax.bitcast_convert_type`` in the program), a bool field as 0 / 1. The host
makes one buffer and one transfer; the step program slices it apart at static
offsets. A layout is a function of the program's static signature (its bucket,
its chunk width), made once where the program is built.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Field(NamedTuple):
    """``name``: the step function's parameter the field becomes; ``cols``:
    0 for one value a row (``[rows]``), n for ``[rows, n]``; ``dtype``: int32,
    float32 or bool; ``fill``: what an idle row holds."""

    name: str
    cols: int
    dtype: Any
    fill: float = 0


def _host_dtype(field: Field):
    return np.float32 if field.dtype == np.float32 else np.int32


class CallLayout:
    def __init__(self, *fields: Field):
        self.fields = fields
        self.offsets: dict[str, int] = {}
        idle: list[np.ndarray] = []
        at = 0
        for field in fields:
            if field.dtype not in (np.int32, np.float32, np.bool_):
                raise TypeError(f"call field {field.name!r}: {field.dtype} "
                                "does not ride in an int32 column")
            self.offsets[field.name] = at
            at += field.cols or 1
            idle.append(np.full((field.cols or 1,), field.fill,
                                _host_dtype(field)).view(np.int32))
        self.width = at
        self._idle_row = np.concatenate(idle)

    def host(self, rows: int) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """A fresh call of ``rows`` idle rows: the buffer to upload, and each
        field as a writable view of it (a float32 field as float32, a bool
        one as int32 0 / 1)."""
        buffer = np.tile(self._idle_row, (rows, 1))
        views = {}
        for field in self.fields:
            at = self.offsets[field.name]
            view = buffer[:, at:at + (field.cols or 1)].view(
                _host_dtype(field))
            views[field.name] = view if field.cols else view[:, 0]
        return buffer, views

    def unpack(self, packed: jax.Array) -> dict[str, jax.Array]:
        """Inside the step program: the fields of ``packed`` [rows, width],
        each in its own dtype."""
        if packed.shape[1] != self.width or packed.dtype != jnp.int32:
            raise ValueError(f"call of {packed.dtype}{list(packed.shape)} "
                             f"against a layout {self.width} int32 wide")
        out = {}
        for field in self.fields:
            at = self.offsets[field.name]
            cols = (packed[:, at:at + field.cols] if field.cols
                    else packed[:, at])
            if field.dtype == np.float32:
                cols = jax.lax.bitcast_convert_type(cols, jnp.float32)
            elif field.dtype == np.bool_:
                cols = cols != 0
            out[field.name] = cols
        return out


# the rows' sampling parameters (sampling.SamplingParams' fields, by name) and
# the dispatch counter: the tail of every layout
CALL_TAIL = (Field("temperature", 0, np.float32, 0.0),
             Field("top_k", 0, np.int32, 0),
             Field("top_p", 0, np.float32, 1.0),
             Field("counter", 0, np.int32, 0))
