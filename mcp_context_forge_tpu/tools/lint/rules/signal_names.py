"""Rule: SignalBus published vs consumed names must agree, tree-wide.

The closed loop is only closed when the engine's publishes and the
controller's reads spell the SAME dotted name: a typo on either side
does not error — the controller reads ``None``, every policy holds
(absent = hold is the designed stale behavior), and the system silently
stops steering. This generalizes the span-stitch rule from trace spans
to the whole signal plane.

Consumed-name extraction handles the tree's three read idioms: direct
literals (``bus.get("llm.spec_accept", rid)``), same-class forwarders
(``self._view("llm.idle_frac", rid)`` → ``bus.get(name, ...)``), and
constant-tuple loops (``for name in self._EFFECT_SIGNALS: bus.ewma(name,
...)``).

Checks:

1. **Read-but-never-published** — a consumed literal no publish site
   (literal or dynamic f-string prefix) produces: the consumer is
   steering on a signal that will never arrive. Fires at the read site.
2. **Published-but-never-read** — fires at the publish site. Signals
   exported only for dashboards via ``SignalBus.snapshot()`` (the
   ``/signals`` endpoint) are legitimate; say so with
   ``# lint: allow[signal-name-conformance] <consumer>``.
3. **Dynamic publish** — an f-string name (``f"slo.burn_rate.{cls}"``)
   is invisible to static conformance on the consumer side; the publish
   site must carry an ``allow[]`` naming its consumer, so the dynamic
   family stays a conscious exception rather than a growing blind spot.

Subset-run degradation: the rule needs BOTH sides of the conversation —
no publish sites or no read sites in the context set means silence, not
a flood of one-sided findings.
"""

from __future__ import annotations

from typing import Iterator

from ..core import FileContext, Finding, Rule, register


@register
class SignalNameConformanceRule(Rule):
    rule_id = "signal-name-conformance"
    description = ("SignalBus names published and consumed must agree "
                   "across the tree")

    def check_graph(self, graph,
                    contexts: list[FileContext]) -> Iterator[Finding]:
        published = graph.signal_published
        read = graph.signal_read
        if (not published and not graph.signal_prefixes) or not read:
            return iter(())
        findings: list[Finding] = []
        prefixes = [p for p, _ in graph.signal_prefixes]

        for name, sites in sorted(read.items()):
            if name in published:
                continue
            if any(name.startswith(p) for p in prefixes):
                continue
            for site in sites:
                findings.append(Finding(
                    self.rule_id, site.path, site.lineno,
                    f"signal {name!r} is consumed here but published "
                    f"nowhere in-tree — the read returns None forever "
                    f"and the policy silently holds"))

        for name, sites in sorted(published.items()):
            if name in read:
                continue
            for site in sites:
                findings.append(Finding(
                    self.rule_id, site.path, site.lineno,
                    f"signal {name!r} is published but no in-tree "
                    f"consumer reads it — name drift or dashboard-only "
                    f"export; fix the name or allow[] with the consumer"))

        for prefix, site in graph.signal_prefixes:
            findings.append(Finding(
                self.rule_id, site.path, site.lineno,
                f"dynamic signal name f\"{prefix}{{...}}\" cannot be "
                f"conformance-checked statically — allow[] with the "
                f"family's consumer so the exception stays conscious"))
        return iter(findings)
