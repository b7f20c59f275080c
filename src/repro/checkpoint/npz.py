"""Checkpointing: pytree <-> npz with sharding-aware host gather.

Flat key encoding: path segments joined with '/'; list indices appear as
'[i]'.  Restoring rebuilds the exact tree structure from the keys, then
(optionally) re-places leaves onto a target sharding tree.

Durability (PR 7): ``save_pytree`` is ATOMIC — it writes ``path + ".tmp"``
and ``os.replace``s it over the final name, so a crash (or ``kill -9``)
mid-save can never destroy the previous checkpoint: readers see either
the old complete file or the new complete file, never a torn one.
``load_pytree`` raises ``CheckpointError`` with a clear message on a
corrupted/truncated file instead of surfacing a zipfile traceback, and
``latest_checkpoint``/``list_checkpoints`` discover cadence-numbered
checkpoints (``<prefix><n>.npz``) so a resuming service can fall back to
the newest VALID file.  Reading a checkpoint into host arrays needs no
JAX (only ``save_pytree`` on device arrays and ``load_pytree(target=)``
import it), so a supervising process can inspect checkpoints without
touching the accelerator.

Service checkpoint schema (``repro.launch.service``, version 1) — a
nested pytree saved through this module:

    flat        (N_hot, F_hot) f32   UE-replica flat buffer
    g           (F_hot,) f32         published cloud model vector
    engine/...                       ``events.AsyncEngine.snapshot()``
                                     (heap_t/edge/cycle, completed,
                                     dep_version, dep_time, version,
                                     delivered, gated, pending_*,
                                     max_staleness, version_tag)
    queue/...                        pending merge jobs (t_arr, t_dep,
                                     edge, cycle, stale, mass, rows)
    svc/...                          scalar control-plane state (clock,
                                     cloud_busy_until, counters,
                                     degraded flag, per-edge dep times)
    metrics/...                      latency/backlog accumulators
    trace_json  0-d unicode          service trace records (JSON)

with ``__meta__/schema`` carrying the service schema version and
``__meta__/config`` the full JSON config echo (validated on resume).
"""
from __future__ import annotations

import os
import re
import zipfile
from typing import Any, List, Optional

import numpy as np


class CheckpointError(RuntimeError):
    """A checkpoint file exists but cannot be read (corrupt/truncated)."""


def _flatten(tree) -> dict:
    flat = {}

    def rec(prefix, node):
        if isinstance(node, dict):
            for k in sorted(node):
                rec(f"{prefix}/{k}" if prefix else str(k), node[k])
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                rec(f"{prefix}[{i}]", v)
        elif node is None:
            flat[prefix + "#none"] = np.zeros((), np.int8)
        else:
            import jax
            flat[prefix] = np.asarray(jax.device_get(node))

    rec("", tree)
    return flat


def save_pytree(path: str, tree, metadata: Optional[dict] = None) -> str:
    """Atomically write ``tree`` (+ optional metadata) as an npz.

    The payload lands in ``path + ".tmp"`` first and is fsync'd, then
    ``os.replace``d over the final name — on any crash the previous
    checkpoint survives intact and at most a ``*.tmp`` orphan is left
    behind (never a torn ``.npz``).  Returns the final path.
    """
    flat = _flatten(tree)
    if metadata:
        for k, v in metadata.items():
            flat[f"__meta__/{k}"] = np.asarray(v)
    final = path if path.endswith(".npz") else path + ".npz"
    os.makedirs(os.path.dirname(os.path.abspath(final)), exist_ok=True)
    tmp = final + ".tmp"
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **flat)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return final


_IDX = re.compile(r"^(.*)\[(\d+)\]$")


def _insert(root, key: str, value):
    """Insert value at the '/'-and-'[i]' encoded path."""
    parts = key.split("/")
    node, parent, pk = root, None, None

    def ensure(container, k, nxt):
        if isinstance(container, dict):
            if k not in container:
                container[k] = nxt
            return container[k]
        while len(container) <= k:
            container.append(None)
        if container[k] is None:
            container[k] = nxt
        return container[k]

    cur = root
    for i, part in enumerate(parts):
        last = i == len(parts) - 1
        steps = []
        m, rest = None, part
        while (m := _IDX.match(rest)):
            rest, idx = m.group(1), int(m.group(2))
            steps.append(idx)
        steps = steps[::-1]
        # rest is the dict key (may be '' if pure index chain)
        chain = ([("d", rest)] if rest else []) + [("l", s) for s in steps]
        for j, (kind, k) in enumerate(chain):
            leaf_here = last and j == len(chain) - 1
            if leaf_here:
                if kind == "d":
                    cur[k] = value
                else:
                    while len(cur) <= k:
                        cur.append(None)
                    cur[k] = value
            else:
                nxt_kind = chain[j + 1][0] if j + 1 < len(chain) else \
                    ("l" if _IDX.match(parts[i + 1]) and not parts[i + 1][0].isalpha() else "d")
                nxt = [] if nxt_kind == "l" else {}
                cur = ensure(cur, k, nxt)
    return root


def load_pytree(path: str, target: Any = None):
    """Load an npz checkpoint.  If ``target`` (a pytree of arrays or
    ShapeDtypeStructs with .sharding) is given, leaves are device_put onto
    the matching shardings and the tree structure is taken from target."""
    p = path if path.endswith(".npz") else path + ".npz"
    if not os.path.exists(p):
        raise FileNotFoundError(p)
    try:
        # np.load on an npz is lazy per entry; force every member through
        # so truncation anywhere in the archive surfaces HERE, as one
        # clear CheckpointError, not as a zipfile traceback at first use.
        data = np.load(p, allow_pickle=False)
        flat = {k: data[k] for k in data.files
                if not k.startswith("__meta__/")}
        meta = {k[len("__meta__/"):]: data[k] for k in data.files
                if k.startswith("__meta__/")}
    except (zipfile.BadZipFile, ValueError, EOFError, OSError, KeyError) as e:
        raise CheckpointError(
            f"checkpoint {p} is corrupted or truncated ({e}).  Saves are "
            f"atomic (tmp+rename), so this file was damaged after the "
            f"write — or predates the atomic writer; fall back to an "
            f"earlier checkpoint (see list_checkpoints).") from e

    if target is not None:
        import jax
        leaves, treedef = jax.tree.flatten(target)
        keys = sorted(flat)
        assert len(keys) == len(leaves), (len(keys), len(leaves))
        new = []
        for k, tgt in zip(keys, leaves):
            arr = flat[k]
            sh = getattr(tgt, "sharding", None)
            new.append(jax.device_put(arr, sh) if sh is not None else arr)
        return jax.tree.unflatten(treedef, new), meta

    root: dict = {}
    for k, v in sorted(flat.items()):
        if k.endswith("#none"):
            _insert(root, k[:-5], None)
        else:
            _insert(root, k, v)
    return root, meta


# ---------------------------------------------------------------------------
# Cadence-numbered checkpoint discovery (the always-on service).
# ---------------------------------------------------------------------------

_CKPT = re.compile(r"^(?P<prefix>.*?)(?P<num>\d+)\.npz$")


def list_checkpoints(ckpt_dir: str, prefix: str = "ckpt-") -> List[str]:
    """Paths of ``<prefix><n>.npz`` files in ``ckpt_dir``, ascending by
    ``n``.  ``*.tmp`` orphans (crashed mid-save) are ignored.  Returns
    ``[]`` for a missing or empty directory."""
    if not os.path.isdir(ckpt_dir):
        return []
    found = []
    for name in os.listdir(ckpt_dir):
        m = _CKPT.match(name)
        if m and m.group("prefix") == prefix:
            found.append((int(m.group("num")), name))
    return [os.path.join(ckpt_dir, name) for _, name in sorted(found)]


def latest_checkpoint(ckpt_dir: str, prefix: str = "ckpt-") -> Optional[str]:
    """Newest cadence-numbered checkpoint path, or None.

    Purely name-based — pair with ``load_pytree``'s ``CheckpointError``
    and fall back through ``list_checkpoints`` when the newest file turns
    out to be damaged."""
    paths = list_checkpoints(ckpt_dir, prefix)
    return paths[-1] if paths else None


def gc_checkpoints(ckpt_dir: str, keep_last_k: int,
                   prefix: str = "ckpt-") -> List[str]:
    """Compact the cadence directory down to the newest ``keep_last_k``
    checkpoints.  Returns the paths it deleted (oldest first).

    Crash safety rests on the DELETION ORDER: victims are removed oldest
    first (delete-newest-last), so a crash at ANY point of the delete
    sequence leaves the surviving files as a suffix of the cadence — the
    newest ``keep_last_k`` generations are intact and every gap sits
    strictly BELOW the oldest survivor.  ``restore_latest``-style readers
    (newest first, falling back on ``CheckpointError``) therefore always
    find the same restore frontier they would have found had the GC
    completed; an interrupted GC only means the next GC pass has more
    old files to collect.

    A missing victim (already collected by a concurrent/previous pass)
    is skipped, not an error.  ``keep_last_k`` must be >= 1 — a GC that
    could delete the newest checkpoint would defeat the whole durability
    story; disable GC by not calling this instead.
    """
    if keep_last_k < 1:
        raise ValueError(f"keep_last_k must be >= 1 to garbage-collect "
                         f"(the newest checkpoint is never deletable), "
                         f"got {keep_last_k}")
    paths = list_checkpoints(ckpt_dir, prefix)
    deleted: List[str] = []
    for path in paths[:-keep_last_k]:     # ascending: oldest deleted first
        try:
            os.remove(path)
        except FileNotFoundError:
            continue
        deleted.append(path)
    return deleted
