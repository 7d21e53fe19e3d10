"""Lock-ownership pass (rules lock-guarded-attr / lock-wait-while /
lock-blocking-call).

``DEFAULT_LOCK_MAP`` below is THE guarded-attribute map: the single
source of truth for which ``self.*`` state each serve class may only
touch under its lock.  DESIGN.md §8's concurrency model and the runtime
sanitizer (tools.analysis.runtime) both defer to it — edit it here, not
in prose.

Semantics are lexical, matching how the serve layer is written:

- an attribute access is "guarded" when a ``with self.<lock>`` block
  encloses it *within the same function body* (a nested ``def``/
  ``lambda`` resets guarding — the closure runs later, lock not held);
- ``__init__`` is exempt: construction happens-before any thread that
  could contend (the same happens-before the CPython memory model gives
  ``Thread.start``);
- ``<lock>.wait(...)`` must have a ``while`` ancestor in the same
  function (the repo-wide spurious-wakeup discipline);
- inside a ``with self.<lock>`` body, calls whose terminal name is in
  ``BLOCKING_NAMES`` (or ``.join`` on something that looks like a
  thread) are flagged: blocking under the cv stalls every producer.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Tuple

from tools.analysis.core import Finding, LockSpec, SourceFile, terminal_name

#: path -> lock contracts.  Keep in lock-step with DESIGN.md §10's table.
DEFAULT_LOCK_MAP: Dict[str, Tuple[LockSpec, ...]] = {
    "src/repro/serve/server.py": (
        LockSpec(
            cls="Server",
            lock_attr="_cv",
            guarded=(
                "_running",
                "_draining",
                "_closed",
                "_worker",
                "_worker_work",
                "requests",
            ),
        ),
    ),
    "src/repro/serve/batching.py": (
        LockSpec(
            cls="BucketBatcher",
            lock_attr="_lock",
            guarded=("_q", "_last_t", "_n_deadlined", "_rid", "_batches"),
        ),
    ),
}

#: Terminal call names that block: device compute / host transfers /
#: sleeps / the serve layer's own dispatch helpers.
BLOCKING_NAMES = {
    "sleep",
    "asarray",
    "block_until_ready",
    "device_put",
    "run_bucket",
    "stage",
    "_dispatch",
    "_dispatch_async",
    "_finalize",
    "_complete",
    "_run_batch",
    "_stage_retry",
}
#: ``.join`` is only blocking when the receiver smells like a thread —
#: keeps ``", ".join(...)`` out of the blast radius.
THREADISH_RE = re.compile(r"(worker|thread|producer)|^_?t\d*$", re.I)

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _is_self_attr(node: ast.AST, attr: str) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr == attr
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    )


def _with_guards(node: ast.With, lock_attr: str) -> bool:
    return any(_is_self_attr(item.context_expr, lock_attr) for item in node.items)


def _enclosing_function(sf: SourceFile, node: ast.AST) -> Optional[ast.AST]:
    for anc in sf.ancestors(node):
        if isinstance(anc, _FUNC_NODES):
            return anc
    return None


def _guarded_here(sf: SourceFile, node: ast.AST, lock_attr: str) -> bool:
    """True when a ``with self.<lock_attr>`` encloses ``node`` before any
    intervening function boundary."""
    for anc in sf.ancestors(node):
        if isinstance(anc, _FUNC_NODES):
            return False
        if isinstance(anc, ast.With) and _with_guards(anc, lock_attr):
            return True
    return False


def check(sf: SourceFile, specs: Tuple[LockSpec, ...]) -> List[Finding]:
    findings: List[Finding] = []
    for spec in specs:
        cls = next(
            (
                n
                for n in ast.walk(sf.tree)
                if isinstance(n, ast.ClassDef) and n.name == spec.cls
            ),
            None,
        )
        if cls is None:
            findings.append(
                sf.finding(
                    "lock-guarded-attr",
                    1,
                    f"lock map declares class {spec.cls!r} but this file "
                    f"does not define it — update tools.analysis.locks",
                )
            )
            continue
        guarded = set(spec.guarded)
        for node in ast.walk(cls):
            # --- guarded attribute discipline -------------------------
            if (
                isinstance(node, ast.Attribute)
                and node.attr in guarded
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
            ):
                fn = _enclosing_function(sf, node)
                fn_name = getattr(fn, "name", "<lambda>") if fn else "<class>"
                if fn_name == "__init__":
                    continue
                if not _guarded_here(sf, node, spec.lock_attr):
                    mode = "write" if isinstance(node.ctx, ast.Store) else "read"
                    findings.append(
                        sf.finding(
                            "lock-guarded-attr",
                            node,
                            f"{spec.cls}.{fn_name}: {mode} of guarded "
                            f"self.{node.attr} outside `with "
                            f"self.{spec.lock_attr}`",
                        )
                    )
            if not isinstance(node, ast.Call):
                continue
            name = terminal_name(node.func)
            # --- wait-in-while ---------------------------------------
            if (
                name in ("wait", "wait_for")
                and isinstance(node.func, ast.Attribute)
                and _is_self_attr(node.func.value, spec.lock_attr)
            ):
                if name == "wait" and not _has_while_ancestor(sf, node):
                    findings.append(
                        sf.finding(
                            "lock-wait-while",
                            node,
                            f"{spec.cls}: self.{spec.lock_attr}.wait() "
                            f"without an enclosing while — predicate must "
                            f"be re-checked after spurious wakeups",
                        )
                    )
                continue
            # --- blocking work under the lock ------------------------
            if not _guarded_here(sf, node, spec.lock_attr):
                continue
            if name in BLOCKING_NAMES:
                findings.append(
                    sf.finding(
                        "lock-blocking-call",
                        node,
                        f"{spec.cls}: blocking call {name}() while "
                        f"holding self.{spec.lock_attr}",
                    )
                )
            elif name == "join" and isinstance(node.func, ast.Attribute):
                recv = node.func.value
                recv_name = (
                    recv.attr
                    if isinstance(recv, ast.Attribute)
                    else recv.id
                    if isinstance(recv, ast.Name)
                    else ""
                )
                if THREADISH_RE.search(recv_name):
                    findings.append(
                        sf.finding(
                            "lock-blocking-call",
                            node,
                            f"{spec.cls}: {recv_name}.join() while holding "
                            f"self.{spec.lock_attr} — joining a worker that "
                            f"needs the lock deadlocks",
                        )
                    )
    return findings


def _has_while_ancestor(sf: SourceFile, node: ast.AST) -> bool:
    for anc in sf.ancestors(node):
        if isinstance(anc, _FUNC_NODES):
            return False
        if isinstance(anc, ast.While):
            return True
    return False
