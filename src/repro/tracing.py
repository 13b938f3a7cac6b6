"""Named host spans, per-call tallies and compile counts for the stages.

One small facility, used at every layer boundary of the program:

* ``span(name, **attrs)`` — a context manager around one stage of host
  work. It enters ``jax.profiler.TraceAnnotation(name, **attrs)`` while a
  profiler runs, so the span lands in the same trace as the device ops, on
  the same clock; without a profiler it only keeps this thread's stack of
  open spans and times itself (``start``, ``seconds``). A profiler shows
  the attributes after a ``#`` in the name: match names by the part before
  it.
* ``Tally`` — a per-call record, opened as a context manager on one
  thread: every span that closes on that thread while it is open adds its
  count and host seconds (``spans = {name: [count, seconds]}``), and every
  XLA compilation is charged to the innermost open span
  (``compiles = {name: n}``), counted from one ``jax.monitoring`` listener
  on ``/jax/core/compile/backend_compile_duration``.

There is no switch: a span costs about a microsecond of host time with no
profiler running. Device work is named separately, by ``jax.named_scope``
inside the jitted steps (``zbuild``, ``oracle``, ``comm``);
``hlo_scopes`` maps the instructions of a compiled executable to those
scopes, so device ops in a trace can be charged to them.
"""

from __future__ import annotations

import re
import threading
import time

import jax

__all__ = ["span", "Span", "Tally", "open_spans", "hlo_scopes", "SCOPES",
           "COMPILE_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# device scopes named inside the mode steps, innermost wins (the core
# executables run under one more, ``core``: HooiExecutor.core_ops)
SCOPES = ("zbuild", "oracle", "comm")

_Annotation = jax.profiler.TraceAnnotation
_recording = _Annotation.is_enabled  # True while a profiler records
_clock = time.perf_counter
_new = object.__new__


class _Thread(threading.local):
    def __init__(self):
        self.stack: list[str] = []  # names of the open spans, innermost last
        self.tallies: list[Tally] = []  # open tallies


_THREAD = _Thread()


def open_spans() -> tuple[str, ...]:
    """Names of this thread's open spans, outermost first."""
    return tuple(_THREAD.stack)


class Span:
    """One open or closed span: ``name``, ``attrs``, and once entered its
    ``start`` and, once closed, its ``seconds`` (host clock)."""

    __slots__ = ("name", "attrs", "start", "seconds", "_annotation")

    def __enter__(self) -> "Span":
        _THREAD.stack.append(self.name)
        self._annotation = (_Annotation(self.name, **self.attrs).__enter__()
                            if _recording() else None)
        self.start = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = seconds = _clock() - self.start
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        th = _THREAD
        th.stack.pop()
        for tally in th.tallies:
            rec = tally.spans.get(self.name)
            if rec is None:
                tally.spans[self.name] = [1, seconds]
            else:
                rec[0] += 1
                rec[1] += seconds
        return False


def span(name: str, **attrs) -> Span:
    """One named stage of host work (see the module docstring)."""
    sp = _new(Span)  # a plain call: cheaper than a class call with kwargs
    sp.name = name
    sp.attrs = attrs
    return sp


class Tally:
    """Count and host seconds per span name, and compilations per span,
    of everything that runs on this thread while the tally is open."""

    __slots__ = ("spans", "compiles")

    def __init__(self):
        self.spans: dict[str, list] = {}  # name -> [count, seconds]
        self.compiles: dict[str, int] = {}  # innermost span -> compilations

    def __enter__(self) -> "Tally":
        _THREAD.tallies.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        _THREAD.tallies.remove(self)
        return False


def _on_duration_event(event: str, _duration: float, **_kw) -> None:
    if event != COMPILE_EVENT:
        return
    th = _THREAD
    if not th.tallies or not th.stack:
        return
    name = th.stack[-1]
    for tally in th.tallies:
        tally.compiles[name] = tally.compiles.get(name, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)


# ------------------------------------------------------- device scopes
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*[^=]*?\s"
                    r"[\w\-]+\((.*?)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def _scope_of(op_name: str, names) -> str | None:
    found = None
    for part in op_name.split("/"):
        if part in names:
            found = part
    return found


def hlo_scopes(hlo_text: str, names=SCOPES) -> dict[str, str]:
    """``{"<module>/<instruction>": scope}`` of a compiled HLO module's text.

    An instruction takes the innermost of ``names`` (the mode steps'
    ``SCOPES`` unless given; ``("core",)`` for the core executables) in its
    ``op_name`` metadata (a fusion: its own metadata). One that carries
    none (a copy or layout change XLA inserted) takes the scope of its
    first operand that has one. Instructions left without a scope are not
    listed.
    """
    module = ""
    scopes: dict[str, str] = {}
    operands: dict[str, list] = {}
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        scope = _scope_of(meta.group(1), names) if meta else None
        if scope is not None:
            scopes[name] = scope
        else:
            operands[name] = _OPERAND.findall(m.group(2))
    changed = True
    while changed:  # inherit along chains of unscoped instructions
        changed = False
        for name, ops in list(operands.items()):
            for op in ops:
                if op in scopes:
                    scopes[name] = scopes[op]
                    del operands[name]
                    changed = True
                    break
    return {f"{module}/{name}": s for name, s in scopes.items()}
