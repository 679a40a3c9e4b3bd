"""Unified metrics registry + device-side metric planes (DESIGN.md §11).

The registry is the ONE snapshot surface of the serving stack: every
cache / engine / tenancy telemetry source mounts a *provider* (a callable
returning a possibly-nested dict) under a namespace, and
``Registry.snapshot()`` flattens the whole mounted tree into a flat
``{"ns/sub/key": value}`` dict.  The zero-sync pull protocol: providers
return device arrays UN-pulled (0-d counters, ``(rows,)`` planes,
histograms), and the snapshot performs exactly one batched
``jax.device_get`` over all device leaves — never one sync per key, never
a sync inside a hot loop (satellite: ``tenancy.row_telemetry`` rides the
same single pull).

Device metric planes for the decode loop (``loop_planes`` /
``loop_update``) follow the ``RowCounters`` idiom: a small int32 pytree
carried through the jitted scan (donated alongside the KV caches) and
advanced by integer adds and scatter-adds only, which have no
reassociation freedom, so they count the served tokens exactly and are
bit-identical across row meshes (tests/test_obs.py pins both).

``safe_ratio`` is the one guarded hit-ratio division every surface uses
(prefix cache, expert cache, simulator, tenancy) — the zero-access
telemetry bugfix lives here, once.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "safe_ratio",
    "safe_ratio_plane",
    "Derived",
    "Registry",
    "HIST_BINS",
    "loop_planes",
    "loop_update",
]

#: token-histogram buckets in the decode-loop planes (`loop_planes`)
HIST_BINS = 16


def safe_ratio(num, den) -> float:
    """``num / den`` with the zero-denominator guard every telemetry
    surface shares: 0.0 when ``den`` is falsy (no accesses yet).  Host
    floats in, host float out — exact ``int/int`` float64 division, so
    accounting parity assertions (device counters vs host oracles) can
    compare ratios with ``==``."""
    return num / den if den else 0.0


def safe_ratio_plane(num: jax.Array, den: jax.Array) -> jax.Array:
    """Device-side ``safe_ratio`` over whole planes: float32
    ``num / den`` where ``den > 0``, else 0.0.  Pure and jit-safe (no
    NaN from empty rows — the guard selects the operand, not the
    result)."""
    den_f = jnp.maximum(den.astype(jnp.float32), 1.0)
    out = num.astype(jnp.float32) / den_f
    return jnp.where(den > 0, out, jnp.float32(0.0))


class Derived(NamedTuple):
    """A snapshot value computed on host AFTER the batched device pull,
    from its own namespace group's already-pulled siblings — e.g. an
    exact float64 ``hits / accesses`` over pulled int counters.  ``fn``
    receives a dict of the group's sibling values keyed by their relative
    names (``{"hits": 3, "accesses": 4, ...}``)."""

    fn: Callable[[Dict[str, Any]], Any]


def _flatten(prefix: str, tree: Any, flat: Dict[str, Any]) -> None:
    if isinstance(tree, dict):
        for k, v in tree.items():
            _flatten(f"{prefix}/{k}" if prefix else str(k), v, flat)
    else:
        flat[prefix] = tree


def _scalarize(v: Any) -> Any:
    if isinstance(v, np.ndarray) and v.ndim == 0:
        return v.item()
    if isinstance(v, (np.integer, np.floating)):
        return v.item()
    return v


class Registry:
    """Namespace-mounted metrics registry with a single-pull snapshot.

    ``mount(ns, provider)`` registers a callable returning a (possibly
    nested) dict for namespace ``ns``; ``set_gauge(path, value)`` sets a
    sticky host-side gauge (e.g. the OPT-regret feed) that persists
    across snapshots until overwritten.  ``snapshot()`` evaluates every
    provider, flattens to ``"ns/sub/key"`` paths, pulls ALL device leaves
    in one ``jax.device_get``, resolves ``Derived`` entries from their
    pulled siblings, and returns plain scalars / numpy arrays."""

    def __init__(self):
        self._providers: Dict[str, Callable[[], Dict[str, Any]]] = {}
        self._gauges: Dict[str, Any] = {}

    def mount(self, namespace: str, provider: Callable[[], Dict[str, Any]]) -> None:
        """Register ``provider`` under ``namespace`` (replaces any previous
        mount at the same namespace).  Providers run at snapshot time and
        must not sync the device — return device arrays as-is."""
        self._providers[str(namespace)] = provider

    def unmount(self, namespace: str) -> None:
        """Remove a mounted provider (no-op if absent)."""
        self._providers.pop(str(namespace), None)

    def set_gauge(self, path: str, value: Any) -> None:
        """Set a sticky host-side gauge at flat ``path`` — reported by
        every later ``snapshot()`` until overwritten.  Gauges shadow
        provider values at the same path."""
        self._gauges[str(path)] = value

    def snapshot(self) -> Dict[str, Any]:
        """The flat namespaced snapshot: one dict over every mounted
        provider plus the sticky gauges, with exactly ONE batched
        ``jax.device_get`` for all device leaves (the zero-sync pull
        protocol — DESIGN.md §11).  Device scalars come back as python
        ints/floats, plane/histogram leaves as numpy arrays."""
        flat: Dict[str, Any] = {}
        for ns, provider in self._providers.items():
            _flatten(ns, provider() or {}, flat)
        flat.update(self._gauges)
        device = {k: v for k, v in flat.items() if isinstance(v, jax.Array)}
        pulled = jax.device_get(device) if device else {}
        out: Dict[str, Any] = {}
        derived = []
        for k, v in flat.items():
            if isinstance(v, Derived):
                derived.append((k, v))
            elif k in pulled:
                out[k] = _scalarize(pulled[k])
            else:
                out[k] = _scalarize(v)
        for path, d in derived:
            prefix = path.rsplit("/", 1)[0] + "/" if "/" in path else ""
            group = {
                k[len(prefix):]: v
                for k, v in out.items()
                if k.startswith(prefix) and "/" not in k[len(prefix):]
            }
            out[path] = d.fn(group)
        return out


# ---------------------------------------------------------------------------
# decode-loop metric planes (the RowCounters idiom, engine altitude)
# ---------------------------------------------------------------------------


def loop_planes(bins: int = HIST_BINS) -> Dict[str, jax.Array]:
    """Fresh all-zero decode-loop metric planes: sampled-step and token
    counters (0-d int32) plus a ``(bins,)`` token-id histogram, carried
    through the jitted decode scan (donated with the KV caches)."""
    return {
        "steps": jnp.int32(0),
        "tokens": jnp.int32(0),
        "token_hist": jnp.zeros((bins,), dtype=jnp.int32),
    }


def loop_update(planes: Dict[str, jax.Array], toks: jax.Array, *,
                vocab: int) -> Dict[str, jax.Array]:
    """One sampling event's fold into the loop planes: ``steps += 1``,
    ``tokens += batch``, and a scatter-add into the token histogram
    (bucket = ``tok * bins // vocab``).  Integer ops only, so the fold is
    bit-identical whether it runs inside the decode scan or as a per-step
    jitted call on the host path.  Pure and jit-safe."""
    t = toks.reshape(-1).astype(jnp.int32)
    bins = planes["token_hist"].shape[0]
    b = jnp.clip(t * bins // jnp.int32(vocab), 0, bins - 1)
    return {
        "steps": planes["steps"] + jnp.int32(1),
        "tokens": planes["tokens"] + jnp.int32(t.size),
        "token_hist": planes["token_hist"].at[b].add(jnp.int32(1)),
    }
