"""EXC001 — broad `except Exception` that swallows the error.

A `except Exception:` (or bare `except:` / `except BaseException:`)
whose body neither re-raises nor logs turns real defects — a Pallas
kernel mis-lowering, a device step OOM, a corrupted checkpoint — into
silent behavior changes. The serving engine's step boundary showed the
legitimate shape: catch broadly, but ATTACH the error to the failed
requests. Compliance here is syntactic: the handler body must contain a
`raise`, or a call whose name looks like logging/warning
(`logging.*`, `logger.*`, `warnings.warn`, `traceback.print_exc`,
...). Anything genuinely-broad by design takes
a `# ptlint: disable=EXC001 — <why>` with a one-line justification.
"""
from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding, Project, Rule, dotted

BROAD_TYPES = {"Exception", "BaseException"}


def _looks_like_logging(name: str) -> bool:
    """True for logging/warning-shaped call names: logging.info,
    logger.debug, warnings.warn, traceback.print_exc.
    Segment-anchored so catalog/dialog/backlog don't count as 'log'."""
    for seg in name.split("."):
        s = seg.lower().lstrip("_")
        if s in ("print_exc", "print_exception", "exception"):
            return True
        if s.startswith(("log", "warn")) and s not in ("login", "logout"):
            return True
    return False


def _is_broad(handler: ast.ExceptHandler, resolve) -> bool:
    t = handler.type
    if t is None:
        return True                      # bare `except:`
    if isinstance(t, ast.Tuple):
        return any(_name_is_broad(e, resolve) for e in t.elts)
    return _name_is_broad(t, resolve)


def _name_is_broad(node: ast.AST, resolve) -> bool:
    target = resolve(node)
    if target is None:
        return False
    return target.rsplit(".", 1)[-1] in BROAD_TYPES


def _handles_it(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name and _looks_like_logging(name):
                return True
    return False


class BroadExceptRule(Rule):
    """EXC001: broad `except Exception` whose handler neither re-raises
    nor logs — silent error swallowing."""

    id = "EXC001"
    severity = "warning"
    description = ("broad `except Exception` without re-raise or logging "
                   "swallows real failures")

    def run(self, project: Project) -> Iterator[Finding]:
        for ctx in project.files:
            if ctx.tree is None or not project.focused(ctx.relpath):
                continue
            resolve = ctx.aliases.resolve
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.ExceptHandler):
                    continue
                if not _is_broad(node, resolve):
                    continue
                if _handles_it(node):
                    continue
                what = ("bare `except:`" if node.type is None
                        else f"`except {dotted(node.type) or 'Exception'}`")
                yield ctx.finding(
                    self, node,
                    f"{what} without re-raise or logging — narrow the "
                    f"exception type, or justify with "
                    f"`# ptlint: disable=EXC001 — <why>`")
