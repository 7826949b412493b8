"""The dataplane-primitive registry: one ref + one Pallas impl per primitive.

``dispatch(name, backend)`` is the single switch every hot-path call site
goes through (DESIGN.md §9): ``core/header.crc16_tag``/``tag_valid``,
``Firewall.__call__``, ``MaglevLB.__call__`` and ``core/park``'s payload
movement.  The returned callable is resolved at trace time from the frozen
``BackendConfig``, so jitted programs specialize on the backend exactly as
they specialize on shapes.

The Pallas implementations are imported lazily (inside the wrapper
functions): pure-ref runs never import the kernel layer, and the kernels
are free to import ``repro.backend.ref`` for shared constants without an
import cycle.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

from repro.backend import ref as R
from repro.backend.config import PRIMITIVES, as_config


@dataclasses.dataclass(frozen=True)
class Primitive:
    """One registry entry.  ``pallas`` takes the ref signature plus a
    keyword-only ``interpret`` flag (the two Pallas modes share a body)."""

    name: str
    ref: Callable
    pallas: Callable


def _pallas_crc16_tag(ti, clk, *, interpret: bool):
    from repro.kernels.crc16.ops import crc16_tag_kernel_op
    return crc16_tag_kernel_op(ti, clk, interpret=interpret)


def _pallas_acl_match(src_ip, rules, *, interpret: bool):
    from repro.kernels.acl_match.ops import acl_match
    return acl_match(src_ip, rules, interpret=interpret)


def _pallas_maglev_select(src_ip, dst_ip, src_port, dst_port, proto,
                          table, backend_ips, *, interpret: bool):
    from repro.kernels.maglev.ops import maglev_select
    return maglev_select(src_ip, dst_ip, src_port, dst_port, proto,
                         table, backend_ips, interpret=interpret)


def _pallas_payload_store(table, payload, idx, enb, *,
                          interpret: bool):
    from repro.kernels.payload_store.ops import payload_store
    return payload_store(table, payload, idx, enb, interpret=interpret)


def _pallas_payload_fetch(table, idx, mask, *, interpret: bool):
    from repro.kernels.payload_fetch.ops import payload_fetch
    return payload_fetch(table, idx, mask, interpret=interpret)


_REGISTRY: dict[str, Primitive] = {
    p.name: p for p in (
        Primitive("crc16_tag", R.crc16_tag, _pallas_crc16_tag),
        Primitive("acl_match", R.acl_match, _pallas_acl_match),
        Primitive("maglev_select", R.maglev_select, _pallas_maglev_select),
        Primitive("payload_store", R.payload_store, _pallas_payload_store),
        Primitive("payload_fetch", R.payload_fetch, _pallas_payload_fetch),
    )
}

assert tuple(_REGISTRY) == PRIMITIVES, (tuple(_REGISTRY), PRIMITIVES)


def primitive(name: str) -> Primitive:
    if name not in _REGISTRY:
        raise KeyError(f"unknown primitive {name!r} (have {PRIMITIVES})")
    return _REGISTRY[name]


def dispatch(name: str,
             backend: "BackendConfig | str | None" = None) -> Callable:
    """Resolve one primitive to the callable its backend selects."""
    prim = primitive(name)
    mode = as_config(backend).resolve(name)
    if mode == "ref":
        return prim.ref
    return partial(prim.pallas, interpret=(mode == "pallas_interpret"))
