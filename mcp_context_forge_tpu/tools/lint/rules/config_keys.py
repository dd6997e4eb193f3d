"""Rule: every config knob must be read somewhere and documented.

``Settings`` (config.py) and ``EngineConfig`` have grown to ~100 fields
across 19 PRs. A field nothing reads is dead weight that still LOOKS
tunable — an operator sets it, nothing changes, and the gap between the
config surface and the behavior surface widens silently. A field that IS
read but appears in no ``docs/*.md`` is a knob only its author can
operate.

Checks (both anchored at the field's declaration line):

1. **Dead field** — the attribute name is read as an attribute nowhere
   in-tree. The declaration itself is an ``AnnAssign`` target (a Name,
   never an Attribute) so it cannot satisfy its own check; config.py's
   computed properties (``cors_origins`` parsing ``cors_allowed_origins``)
   and ``getattr(settings, "name", default)`` string literals count as
   reads, and a ``Settings`` field ``tpu_local_<f>`` (or ``<f>``) beside
   an ``EngineConfig`` field ``<f>`` is read where that field is
   (``EngineConfig.from_settings`` fills every field by that rule). Fields read only through f-string getattr (dynamic key
   construction) or kept deliberately (forward-compat) get
   ``# lint: allow[config-key-liveness] <why it stays>``.
2. **Undocumented field** — the name appears nowhere in the
   concatenated ``docs/*.md`` text (whole-word match). Skipped entirely
   when the graph found no docs tree — in-memory fixture runs must not
   flag every knob.

Liveness is by attribute NAME, deliberately over-approximate: a field
named like an unrelated attribute counts as read. False negatives over
false positives — this rule exists to catch knobs NOTHING touches.

Subset-run degradation: no ``Settings``/``EngineConfig`` declaration in
the context set means no registry to check — silence.
"""

from __future__ import annotations

import re
from typing import Iterator

from ..core import FileContext, Finding, Rule, register


@register
class ConfigKeyLivenessRule(Rule):
    rule_id = "config-key-liveness"
    description = ("Settings/EngineConfig fields must be read outside "
                   "their module and documented in docs/")

    def check_graph(self, graph,
                    contexts: list[FileContext]) -> Iterator[Finding]:
        findings: list[Finding] = []
        fields = [("Settings", name, site)
                  for name, site in graph.settings_fields.items()]
        fields += [("EngineConfig", name, site)
                   for name, site in graph.engine_fields.items()]
        if not fields:
            return iter(())

        docs = graph.docs_text
        for owner, name, site in sorted(fields, key=lambda f: (f[2].path,
                                                               f[2].lineno)):
            # any attribute read counts — the declaration itself is an
            # AnnAssign Name, never an Attribute, so it cannot satisfy
            # its own check; config.py-internal reads are computed
            # properties (cors_origins etc.), a legitimate consumption
            readers = graph.attr_reads.get(name, set())
            filled = name.removeprefix("tpu_local_")
            if not readers and owner == "Settings" \
                    and filled in graph.engine_fields:
                # EngineConfig.from_settings fills each field from its
                # setting by rule, not by name: the setting is read where
                # the field it fills is
                readers = graph.attr_reads.get(filled, set())
            if not readers:
                findings.append(Finding(
                    self.rule_id, site.path, site.lineno,
                    f"{owner}.{name} is read by no other in-tree module "
                    f"— a knob that changes nothing; delete it or "
                    f"allow[] with why it must stay"))
                continue  # dead implies undocumented; one finding is enough
            if docs is not None and not re.search(
                    rf"\b{re.escape(name)}\b", docs):
                findings.append(Finding(
                    self.rule_id, site.path, site.lineno,
                    f"{owner}.{name} appears in no docs/*.md — operators "
                    f"cannot discover this knob; document it (value "
                    f"semantics + default) or allow[] with a reason"))
        return iter(findings)
