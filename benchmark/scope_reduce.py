"""Device time by the program's named scopes.

The program wraps its layers' parts in ``jax.named_scope`` (``ssm_scan``,
``attn_core``, ``moe_experts``, ...), and the name reaches every
instruction of the compiled module as a component of its metadata's
``op_name`` (``jit(group_step)/transpose(jvp(...))/checkpoint/
rematted_computation/ssm_scan/mul``).  The profiler names a device
operation by its whole instruction and gives it no scope, so the compiled
text is the one place that says: an instruction belongs to the scope its
``op_name`` names (the innermost, where several are on the path), a
fusion to the scope that most instructions of its computation carry
(computations it calls included, as ``trace_reduce.conv_keys_from_hlo``
resolves them), and an instruction that no scope names to ``-``.

Which scopes a program has is its configuration's to say (the file's
``scopes`` list); nothing here knows a model.

Keys are ``trace_reduce.op_key``'s, so that an event of the trace finds
its instruction of the text.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace_reduce

NO_SCOPE = "-"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")


def scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of ``scopes`` on an ``op_name`` path, or None."""
    found = re.findall(r"(?<![\w])(" + "|".join(map(re.escape, scopes))
                       + r")(?![\w])", op_name)
    return found[-1] if found else None


def _computations(text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) ", line)
            cur = m.group(1) if m and line.rstrip().endswith("{") else None
            if cur:
                comps[cur] = []
        elif cur and _INSTR.match(line):
            comps[cur].append(line)
    return comps


def scope_keys_from_hlo(hlo_texts: Iterable[str], scopes: Sequence[str]
                        ) -> Dict[Tuple[str, Optional[str]], str]:
    """``{op_key: scope}`` for every instruction of the compiled modules
    that one of ``scopes`` claims."""
    out: Dict[Tuple[str, Optional[str]], str] = {}
    for text in hlo_texts:
        comps = _computations(text)
        votes: Dict[str, Counter] = {}

        def count(comp: str, seen: frozenset = frozenset()) -> Counter:
            """Scope -> instructions of ``comp`` and of what it calls."""
            if comp in votes:
                return votes[comp]
            c: Counter = Counter()
            for ln in comps.get(comp, ()):
                m = _OP_NAME.search(ln)
                s = scope_of(m.group(1), scopes) if m else None
                if s:
                    c[s] += 1
                called = trace_reduce._CALLS.search(ln)
                if called and called.group(1) not in seen:
                    c.update(count(called.group(1), seen | {comp}))
            votes[comp] = c
            return c

        for lines in comps.values():
            for ln in lines:
                scope = None
                called = trace_reduce._CALLS.search(ln)
                if " fusion(" in ln and called:
                    best = count(called.group(1)).most_common(1)
                    scope = best[0][0] if best else None
                if scope is None:
                    m = _OP_NAME.search(ln)
                    scope = scope_of(m.group(1), scopes) if m else None
                if scope:
                    out[trace_reduce.op_key(ln.split(", metadata=")[0])] = \
                        scope
    return out


def scope_seconds(ops: Dict[str, float],
                  keys: Dict[Tuple[str, Optional[str]], str]
                  ) -> Dict[str, float]:
    """Self seconds a device by scope, from ``reduce(...)["ops"]`` (whole
    instruction -> self seconds); what no scope claims is under ``-``."""
    out: Dict[str, float] = {}
    for name, secs in ops.items():
        s = keys.get(trace_reduce.op_key(name), NO_SCOPE)
        out[s] = out.get(s, 0.0) + secs
    return out


def breakdown(ops: Dict[str, float],
              keys: Dict[Tuple[str, Optional[str]], str],
              top: int = trace_reduce.TOP) -> List[list]:
    """The longest device operations, each with its scope in front:
    ``[["ssm_scan/fusion.12 kLoop f32[...]", seconds], ...]``."""
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return [[keys.get(trace_reduce.op_key(name), NO_SCOPE) + "/"
             + trace_reduce.short_label(name), secs]
            for name, secs in ranked]
