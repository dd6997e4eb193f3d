"""Behavioral oracles for the mutation campaigns.

Each oracle is a dense re-statement of a module's CONTRACT (not its code):
it must pass on the real module and fail on any single-fault mutant that
changes observable behavior. Targets are the pure-logic, security-critical
modules where a silent fault is most expensive — JSON-RPC validation and
the RBAC permission check (reference gates the same surfaces through its
mutmut run, `run_mutmut.py`).

Oracles signal a killed mutant by raising — plain ``assert`` is their
mechanism, not auth enforcement, and they never run under ``python -O``.
# seclint: file-allow S008
"""

from __future__ import annotations

import ast
import types
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable

from .mutation import CampaignReport, run_campaign

_PKG_ROOT = Path(__file__).resolve().parent.parent


def _class_line_range(source: str, class_name: str) -> tuple[int, int]:
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            return node.lineno, node.end_lineno or node.lineno
    raise ValueError(f"class {class_name} not found")


@dataclass
class MutationTarget:
    rel_path: str                 # package-relative source path
    module_name: str
    package: str
    oracle: Callable[[types.ModuleType], None]
    class_name: str | None = None  # restrict campaign to this class
    equivalent_lines: frozenset[int] = field(default_factory=frozenset)
    # CONTENT-anchored equivalence exemptions: a surviving mutant whose
    # ORIGINAL source line contains one of these substrings is accepted
    # as behaviorally equivalent. Use these instead of equivalent_lines —
    # absolute line numbers silently stop exempting (or exempt the WRONG
    # line) whenever unrelated edits shift the file.
    equivalent_markers: tuple[str, ...] = ()

    def source(self) -> str:
        """THE source the campaign mutates — every equivalence check
        must read the same bytes (one derivation, three call sites)."""
        return (_PKG_ROOT / self.rel_path).read_text()

    def is_equivalent(self, lineno: int, source: str | None = None) -> bool:
        if lineno in self.equivalent_lines:
            return True
        lines = (source if source is not None
                 else self.source()).splitlines()
        if not (1 <= lineno <= len(lines)):
            return False
        line = lines[lineno - 1]
        return any(marker in line for marker in self.equivalent_markers)

    def run(self) -> CampaignReport:
        source = self.source()
        line_range = (_class_line_range(source, self.class_name)
                      if self.class_name else None)
        return run_campaign(self.module_name, source, self.package, self.oracle,
                            line_range=line_range)


# --------------------------------------------------------------- jsonrpc

def jsonrpc_oracle(mod: types.ModuleType) -> None:
    # exact wire constants
    assert mod.PARSE_ERROR == -32700
    assert mod.INVALID_REQUEST == -32600
    assert mod.METHOD_NOT_FOUND == -32601
    assert mod.INVALID_PARAMS == -32602
    assert mod.INTERNAL_ERROR == -32603
    assert mod.REQUEST_CANCELLED == -32800
    assert mod.CONTENT_TOO_LARGE == -32801
    assert mod.UPSTREAM_UNAVAILABLE == -32003

    E = mod.JSONRPCError

    def rejects(payload, code=mod.INVALID_REQUEST):
        try:
            mod.RPCRequest.parse(payload)
        except E as exc:
            assert exc.code == code, (payload, exc.code)
        else:
            raise AssertionError(f"accepted {payload!r}")

    # JSONRPCError shape
    err = E(-32000, "boom").to_dict("id1")
    assert err == {"jsonrpc": "2.0", "id": "id1",
                   "error": {"code": -32000, "message": "boom"}}
    err = E(-32000, "boom", data={"k": 1}).to_dict(None)
    assert err["error"]["data"] == {"k": 1} and err["id"] is None
    assert mod.error_response(3, -32601, "nf")["error"]["code"] == -32601
    assert mod.result_response(7, {"ok": 1}) == {
        "jsonrpc": "2.0", "id": 7, "result": {"ok": 1}}

    # request validation
    rejects(None)
    rejects([])
    rejects("x")
    rejects({})                                   # no jsonrpc
    rejects({"jsonrpc": "2.0"})                   # no method
    rejects({"jsonrpc": "1.0", "method": "ping"})
    rejects({"jsonrpc": "2.0", "method": ""})
    rejects({"jsonrpc": "2.0", "method": 7})
    rejects({"jsonrpc": "2.0", "method": "m", "params": 3})
    rejects({"jsonrpc": "2.0", "method": "m", "params": "s"})
    rejects({"jsonrpc": "2.0", "method": "m", "id": True})
    rejects({"jsonrpc": "2.0", "method": "m", "id": {}})
    rejects({"jsonrpc": "2.0", "method": "m", "id": []})

    direct = mod.RPCRequest(method="m")   # direct construction = a call
    assert direct.is_notification is False and direct.params == {}

    r = mod.RPCRequest.parse({"jsonrpc": "2.0", "method": "ping", "id": 1})
    assert (r.method, r.id, r.is_notification, r.params) == ("ping", 1, False, {})
    r = mod.RPCRequest.parse({"jsonrpc": "2.0", "method": "n"})
    assert r.is_notification and r.id is None
    r = mod.RPCRequest.parse({"jsonrpc": "2.0", "method": "m", "id": None})
    assert not r.is_notification          # explicit null id is still a request
    r = mod.RPCRequest.parse({"jsonrpc": "2.0", "method": "m", "id": "s",
                              "params": None})
    assert r.params == {} and r.id == "s"
    r = mod.RPCRequest.parse({"jsonrpc": "2.0", "method": "m", "id": 1.5,
                              "params": [1, 2]})
    assert r.params == {"__args__": [1, 2]} and r.id == 1.5
    r = mod.RPCRequest.parse({"jsonrpc": "2.0", "method": "m",
                              "params": {"a": 1}})
    assert r.params == {"a": 1}

    # body parsing + size cap
    assert mod.parse_body(b'{"a": 1}') == {"a": 1}
    assert mod.parse_body(b"[1]", max_size=3) == [1]
    try:
        mod.parse_body(b"[1, 2]", max_size=3)
    except E as exc:
        assert exc.code == mod.CONTENT_TOO_LARGE
    else:
        raise AssertionError("size cap not enforced")
    assert mod.parse_body(b"[1, 2]") == [1, 2]   # default: no cap
    try:
        mod.parse_body(b"{nope")
    except E as exc:
        assert exc.code == mod.PARSE_ERROR
    else:
        raise AssertionError("parse error not raised")

    # response-message detection (elicitation replies on the POST channel)
    assert mod.is_response_message({"id": 1, "result": {}})
    assert mod.is_response_message({"id": 1, "error": {"code": -1}})
    assert not mod.is_response_message({"id": 1, "method": "m", "result": {}})
    assert not mod.is_response_message({"id": 1})
    assert not mod.is_response_message([1])
    assert not mod.is_response_message("x")

    # method registry
    reg = mod.MCPMethodRegistry()
    assert reg.is_known("tools/call") and reg.is_known("initialize")
    assert reg.is_known("notifications/cancelled")
    assert not reg.is_known("bogus/method")
    reg.register("x/custom")
    assert reg.is_known("x/custom")
    assert reg.is_notification("notifications/anything")
    assert not reg.is_notification("tools/list")
    assert not reg.is_notification("x-notifications/foo")
    for m in ("ping", "tools/list", "tools/call", "resources/list",
              "resources/read", "resources/subscribe", "resources/unsubscribe",
              "resources/templates/list", "prompts/list", "prompts/get",
              "roots/list", "completion/complete", "sampling/createMessage",
              "elicitation/create", "logging/setLevel"):
        assert m in mod.CORE_METHODS, m
    for m in ("notifications/initialized", "notifications/progress",
              "notifications/message", "notifications/roots/list_changed",
              "notifications/tools/list_changed",
              "notifications/resources/list_changed",
              "notifications/resources/updated",
              "notifications/prompts/list_changed"):
        assert m in mod.NOTIFICATION_METHODS, m


# ------------------------------------------------- RoleGrantResolver (RBAC)

def role_resolver_oracle(mod: types.ModuleType) -> None:
    """Contract of role-assignment permission resolution (role_service.py):
    global grants always apply, team grants only with membership, grants
    never escape the catalog, and no scope ever leaks across teams."""
    resolve = mod.RoleGrantResolver.resolve
    catalog = {"a.read", "a.write", "b.read", "c.run"}
    rows = [
        {"scope": "global", "scope_id": "", "permissions": '["a.read"]'},
        {"scope": "team", "scope_id": "t1", "permissions": '["a.write"]'},
        {"scope": "team", "scope_id": "t2", "permissions": '["b.read"]'},
        {"scope": "global", "scope_id": "", "permissions": '["ghost.perm"]'},
    ]
    assert resolve(rows, ["t1"], catalog) == {"a.read", "a.write"}
    assert resolve(rows, [], catalog) == {"a.read"}
    assert resolve(rows, ["t2"], catalog) == {"a.read", "b.read"}
    assert resolve(rows, ["t1", "t2"], catalog) == {"a.read", "a.write",
                                                    "b.read"}
    assert resolve(rows, ["t3"], catalog) == {"a.read"}
    assert resolve([], ["t1"], catalog) == set()
    # multi-permission rows resolve in full; catalog intersection applies
    many = [{"scope": "global", "scope_id": "",
             "permissions": '["a.read", "c.run", "x.never"]'}]
    assert resolve(many, [], catalog) == {"a.read", "c.run"}
    # a team grant needs BOTH conditions: team scope AND membership — a
    # global row with a stray scope_id must still apply
    stray = [{"scope": "global", "scope_id": "tX",
              "permissions": '["b.read"]'}]
    assert resolve(stray, [], catalog) == {"b.read"}


# ----------------------------------------------------- AuthContext (RBAC)

def auth_context_oracle(mod: types.ModuleType) -> None:
    AC = mod.AuthContext

    # plain user: only granted permissions; no spurious rotation flag
    # (a default-True flag would lock every identity out of the surface)
    user = AC(user="u@x", permissions={"tools.read"})
    assert user.password_change_required is False
    assert AC(user="u@x", password_change_required=True
              ).password_change_required is True
    assert user.can("tools.read")
    assert not user.can("tools.delete")
    assert not user.can("admin.all")
    user.require("tools.read")
    try:
        user.require("tools.delete")
    except mod.PermissionDenied:
        pass
    else:
        raise AssertionError("require() let a denied permission through")

    # admin shortcut applies ONLY to unscoped identities
    admin = AC(user="a@x", is_admin=True)
    assert admin.can("tools.delete") and admin.can("anything.at.all")

    # scoped token minted by an admin must NOT inherit admin power
    scoped = AC(user="a@x", is_admin=True, scoped=True,
                permissions={"tools.read"})
    assert scoped.can("tools.read")
    assert not scoped.can("tools.delete")
    assert not scoped.can("admin.all")

    # a scoped token that explicitly carries admin.all is a real admin token
    scoped_admin = AC(user="a@x", is_admin=False, scoped=True,
                      permissions={"admin.all"})
    assert scoped_admin.can("tools.delete")

    # admin.all grant acts as wildcard for unscoped users too
    granted = AC(user="u@x", permissions={"admin.all"})
    assert granted.can("plugins.manage")

    # defaults
    anon = AC(user="anon")
    assert not anon.can("tools.read")
    assert anon.via == "jwt" and not anon.scoped and not anon.is_admin
    assert anon.token_jti is None and anon.server_id is None


# ------------------------------------------------- int8 quantization

def quantize_oracle(mod: types.ModuleType) -> None:
    """Behavioral spec of quantize.py: exact scales, exact rounding, both
    matmul forms, gather, rule mapping. A surviving mutant here means a
    silent numerics fault in the serving weight path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    w = np.array([[1.0, -2.0], [3.0, 0.5], [-0.25, 4.0]], np.float32)
    leaf = mod.quantize_leaf(w, axis=0)
    assert leaf["q"].dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(leaf["s"]),
                               [3.0 / 127, 4.0 / 127], rtol=1e-6)
    scales = np.asarray(leaf["s"])
    np.testing.assert_array_equal(
        np.asarray(leaf["q"]),
        np.round(w / scales[None]).astype(np.int8))
    recon = np.asarray(leaf["q"], np.float32) * scales[None]

    # all-zero weights hit the epsilon clamp EXACTLY (no zero-division)
    tiny = mod.quantize_leaf(np.zeros((2, 2), np.float32), axis=0)
    np.testing.assert_allclose(np.asarray(tiny["s"]), np.float32(1e-8),
                               rtol=0)

    # qmm: quant path equals x @ reconstruction; plain path exact
    x = jnp.asarray(np.array([[1.0, 0.0, 2.0]], np.float32))
    np.testing.assert_allclose(np.asarray(mod.qmm(x, leaf)),
                               np.asarray(x) @ recon, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(mod.qmm(x, jnp.asarray(w))),
                               np.asarray(x @ jnp.asarray(w)), rtol=1e-6)

    # per-ROW table (embedding) + transposed head form
    emb = np.array([[1.0, 2.0], [3.0, -4.0], [0.5, 0.25]], np.float32)
    leaf_e = mod.quantize_leaf(emb, axis=1)
    recon_e = (np.asarray(leaf_e["q"], np.float32)
               * np.asarray(leaf_e["s"])[:, None])
    xt = jnp.asarray(np.array([[1.0, -1.0]], np.float32))
    np.testing.assert_allclose(np.asarray(mod.qmm_t(xt, leaf_e)),
                               np.asarray(xt) @ recon_e.T, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(mod.qmm_t(xt, jnp.asarray(emb))),
                               np.asarray(xt) @ emb.T, rtol=1e-6)

    # ... left in ``out_dtype``: float32 logits from bfloat16 operands (the
    # values of the bfloat16 inputs, no rounding of the product), quantized
    # and plain, over a [B, S, D] input too
    xb = jnp.asarray(np.array([[[1.0, -1.0], [0.5, 3.0]]], np.float32),
                     jnp.bfloat16)
    for table, want in ((leaf_e, recon_e), (jnp.asarray(emb), emb)):
        out = mod.qmm_t(xb, table, jnp.float32)
        assert out.dtype == jnp.float32 and out.shape == (1, 2, 3)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(xb, np.float32) @ want.T, rtol=1e-6)
    assert mod.qmm_t(xb, leaf_e).dtype == jnp.bfloat16     # without it: x's

    # gather: quantized rows reconstruct; plain rows pass through exactly
    rows = np.asarray(mod.embed_rows(leaf_e, jnp.asarray([2, 0])))
    np.testing.assert_allclose(rows, recon_e[[2, 0]], rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(mod.embed_rows(jnp.asarray(emb), jnp.asarray([1]))),
        emb[[1]])

    # discrimination + rule mapping
    assert mod.is_quant(leaf)
    assert not mod.is_quant(w) and not mod.is_quant({"q": 1})
    logical = mod.quantize_logical({"embed": "vocab_in",
                                    "norm": "replicated"})
    assert logical["embed"] == {"q": "vocab_in", "s": "scale_model"}
    assert logical["norm"] == "replicated"
    tree = mod.quantize_tree({"embed": emb,
                              "norm": np.ones((3,), np.float32)},
                             {"embed": "vocab_in", "norm": "replicated"})
    assert mod.is_quant(tree["embed"]) and not mod.is_quant(tree["norm"])
    # vocab_in reduces along axis 1 (per-ROW scales): must match leaf_e
    np.testing.assert_array_equal(np.asarray(tree["embed"]["q"]),
                                  np.asarray(leaf_e["q"]))
    # every matmul-weight rule reduces along axis 0 (per-OUT-channel)
    for name in ("vocab_out", "attn_qkv", "attn_out", "ffn_up", "ffn_down"):
        out = mod.quantize_tree({"w": w}, {"w": name})
        np.testing.assert_array_equal(np.asarray(out["w"]["q"]),
                                      np.asarray(leaf["q"]))
        expected_scale = ("scale_model"
                          if name in ("vocab_out", "attn_qkv", "ffn_up")
                          else "replicated")
        assert mod.quantize_logical({"w": name})["w"]["s"] == expected_scale

    abstract = jax.eval_shape(lambda: {"a": jnp.zeros((4,), jnp.int8),
                                       "b": jnp.zeros((2,), jnp.float32)})
    assert mod.param_bytes(abstract) == 4 + 8


# ---------------------------------------------------- RateLimiter

def rate_limiter_oracle(mod: types.ModuleType) -> None:
    """Token-bucket semantics: burst honored exactly, refill at rps,
    recency-ordered eviction, rps<=0 disables. A surviving mutant is a
    silent DoS-protection fault."""
    import time as _time

    RL = mod.RateLimiter

    # burst: exactly `burst` immediate requests pass, the next fails
    limiter = RL(rps=1, burst=3)
    assert [limiter.allow("k") for _ in range(4)] == [True, True, True, False]

    # refill: advance time by 2s at 5 rps -> 10 tokens, capped at burst 3
    limiter = RL(rps=5, burst=3)
    for _ in range(3):
        assert limiter.allow("k")
    assert not limiter.allow("k")
    tokens, last = limiter._buckets["k"]
    limiter._buckets["k"] = (tokens, last - 2.0)  # simulate 2s elapsed
    results = [limiter.allow("k") for _ in range(4)]
    assert results == [True, True, True, False], results

    # independent buckets per key
    limiter = RL(rps=1, burst=1)
    assert limiter.allow("a")
    assert limiter.allow("b")
    assert not limiter.allow("a")

    # disabled limiter always allows and stores nothing
    off = RL(rps=0, burst=1)
    assert all(off.allow("x") for _ in range(5))
    assert not off._buckets

    # recency-ordered eviction: oldest-seen key leaves first
    limiter = RL(rps=1, burst=1, max_buckets=3)
    for key in ("k0", "k1", "k2"):
        limiter.allow(key)
    limiter.allow("k0")          # refresh k0
    limiter.allow("k3")          # overflow -> evict k1 (oldest)
    assert "k1" not in limiter._buckets
    assert {"k0", "k2", "k3"} <= set(limiter._buckets)
    assert len(limiter._buckets) == 3

    # sweep prunes only refilled-to-full buckets (back-dated timestamps —
    # no wall-clock sleeps in a per-mutant campaign)
    limiter = RL(rps=100, burst=1)
    now = _time.monotonic()
    limiter._buckets["gone"] = (0.0, now - 1.0)   # refilled to full long ago
    limiter._buckets["hot"] = (0.0, now + 100)    # never full
    limiter._sweep(now)
    assert "gone" not in limiter._buckets
    assert "hot" in limiter._buckets
    # boundary: an EXACTLY-full bucket is state-free and must prune (the
    # documented sweep contract — recreating it at full burst is identical)
    limiter = RL(rps=1, burst=2)
    now = _time.monotonic()
    limiter._buckets["edge"] = (2.0, now)
    limiter._sweep(now)
    assert "edge" not in limiter._buckets


# -------------------------------------------------- PageAllocator

def page_allocator_oracle(mod: types.ModuleType) -> None:
    """KV-page bookkeeping spec: capacity math, refcounted sharing,
    prefix chains, LRU eviction, slot moves, trash-page reservation. A
    surviving mutant is silent KV corruption or a page leak."""
    PA = mod.PageAllocator

    # capacity: page 0 reserved, ceil-division page math
    alloc = PA(num_pages=8, page_size=4, max_slots=4, max_pages_per_slot=4)
    assert alloc.free_pages == 7 and alloc.pages_in_use == 0
    assert alloc.peak_pages_in_use == 0   # nothing allocated yet
    assert alloc.pages_needed(1) == 1 and alloc.pages_needed(4) == 1
    assert alloc.pages_needed(5) == 2
    assert alloc.can_allocate(28) and not alloc.can_allocate(29)

    # allocation consumes exactly ceil(tokens/page) pages; page 0 never
    # hands out
    assert alloc.allocate_slot(0, 9)  # 3 pages
    assert alloc.pages_in_use == 3 and alloc.free_pages == 4
    assert alloc.peak_pages_in_use == 3   # high-water mark tracks
    assert 0 not in alloc._slots[0]

    # per-slot cap enforced
    assert not alloc.allocate_slot(1, 17)  # 5 pages > max_pages_per_slot
    # pool exhaustion enforced
    assert alloc.allocate_slot(1, 16)      # 4 pages -> pool empty
    assert alloc.free_pages == 0
    assert not alloc.allocate_slot(2, 1)

    # growth happens by whole pages and respects both caps: grow_slot
    # returns the granted token capacity (pages * page_size)
    alloc.free_slot(1)
    assert alloc.free_pages == 4
    assert alloc.grow_slot(0, 12) >= 12    # still 3 pages
    assert alloc.pages_in_use == 3
    assert alloc.grow_slot(0, 13) >= 13    # grows to 4
    assert alloc.pages_in_use == 4
    assert alloc.grow_slot(0, 17) < 17     # per-slot cap
    assert alloc.pages_in_use == 4

    # free returns everything; the peak is MONOTONIC (a bench reading it
    # after the run must see the high-water mark, not the final state)
    alloc.free_slot(0)
    assert alloc.pages_in_use == 0 and alloc.free_pages == 7
    assert alloc.peak_pages_in_use == 7

    # prefix chains: register full pages, probe is read-only, match
    # refcounts, shared pages survive the owner's free
    alloc = PA(num_pages=8, page_size=4, max_slots=4, max_pages_per_slot=4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]          # 2 full pages + 1 token
    assert alloc.allocate_slot(0, len(prompt))
    alloc.register_prefix(0, prompt)
    assert alloc.cached_pages == 2
    before_refs = dict(alloc._ref)
    before_in_use = alloc.pages_in_use
    assert alloc.probe_prefix(prompt) == 8        # full pages only
    assert alloc.pages_in_use == before_in_use    # probe took nothing
    assert alloc._ref == before_refs              # ...not even a refcount
    # a prompt sharing ONE page matches one page
    assert alloc.probe_prefix([1, 2, 3, 4, 99, 98, 97, 96, 95]) == 4
    # the last token never matches (at least one must prefill)
    assert alloc.probe_prefix([1, 2, 3, 4]) == 0

    hist, shared = alloc.match_prefix(prompt)
    assert hist == 8 and len(shared) == 2
    assert alloc.allocate_slot(1, len(prompt), prefix_pages=shared)
    assert alloc.prefix_hits == 1 and alloc.prefix_hit_tokens == 8
    # shared pages counted once, refcounted at exactly 2
    assert alloc.pages_in_use == 3 + 1 + 2 - 2    # 3 owner + 1 fresh
    assert alloc._ref[shared[0]] == 2
    alloc.free_slot(0)                            # owner leaves...
    assert alloc._ref[shared[0]] == 1             # one reference released
    table = alloc.tables()
    import numpy as np
    assert int(np.asarray(table)[1, 0]) == shared[0]  # ...sharer keeps pages

    # unmatched release drops the references again
    hist2, shared2 = alloc.match_prefix(prompt)
    assert hist2 == 8
    alloc.release_prefix(shared2)
    alloc.free_slot(1)
    # refcount zero + registered -> pages stay warm on the LRU, so the
    # free list alone shrinks but free_pages (incl. evictable) is full
    assert alloc.free_pages == 7
    # matching LRU-RESIDENT pages (ref entry deleted at zero) starts the
    # count from scratch: exactly one reference per matched page
    hist3, shared3 = alloc.match_prefix(prompt)
    assert hist3 == 8 and alloc._ref[shared3[0]] == 1
    alloc.release_prefix(shared3)
    assert alloc.free_pages == 7
    # an allocation EXACTLY covered by shared pages (zero fresh) is valid
    hist4, shared4 = alloc.match_prefix(prompt)
    assert alloc.allocate_slot(2, 8, prefix_pages=shared4)
    assert alloc.pages_in_use == 2
    alloc.free_slot(2)

    # eviction: allocation pressure reclaims LRU cache pages
    for slot in range(3):
        assert alloc.allocate_slot(slot, 8)       # 6 pages; evicts cache
    assert alloc.allocate_slot(3, 4)              # the 7th page
    assert alloc.free_pages == 0
    assert alloc.cached_pages <= 1                # chain broken by eviction


def _state_row_spec(mod: types.ModuleType) -> None:
    """State-row contract (families with per-sequence pools): a slot is
    dealt exactly one row beside its pages, never row 0 (the trash row), and
    the row returns to the free list with the slot. A surviving mutant is two sequences sharing a recurrent
    state, or a row leak that runs the pool dry."""
    import numpy as np

    PA = mod.PageAllocator
    plain = PA(num_pages=8, page_size=4, max_slots=3, max_pages_per_slot=4)
    assert plain.state_rows == 0 and plain.allocate_slot(0, 4)
    assert plain.slot_row(0) == 0 and plain.rows_in_use == 0
    assert not np.asarray(plain.state_row_table()).any()

    alloc = PA(num_pages=8, page_size=4, max_slots=3, max_pages_per_slot=4,
               state_rows=4)
    assert alloc.slot_row(1) == 0 and alloc.rows_in_use == 0
    for slot in range(3):
        assert alloc.allocate_slot(slot, 4)
    rows = [alloc.slot_row(slot) for slot in range(3)]
    assert sorted(rows) == [1, 2, 3] and rows[0] == 1   # every row but trash
    assert alloc.rows_in_use == 3
    assert np.asarray(alloc.state_row_table()).tolist() == rows
    # growing a slot keeps its row; freeing returns it for the next tenant
    assert alloc.grow_slot(1, 8) == 8 and alloc.slot_row(1) == rows[1]
    alloc.free_slot(1)
    assert alloc.slot_row(1) == 0 and alloc.rows_in_use == 2
    assert np.asarray(alloc.state_row_table()).tolist() == [rows[0], 0, rows[2]]
    alloc.free_slot(1)                           # freeing twice adds no row
    assert alloc.allocate_slot(1, 4) and alloc.slot_row(1) == rows[1]
    for slot in range(3):
        alloc.free_slot(slot)
    assert alloc.rows_in_use == 0
    for slot in range(3):                        # a full second round fits
        assert alloc.allocate_slot(slot, 4)
    assert sorted(alloc.slot_row(slot) for slot in range(3)) == [1, 2, 3]


def _dirty_tracking_spec(mod: types.ModuleType) -> None:
    """Dirty-row contract: the engine skips the block-table upload iff no
    row changed, so a mutant that over- or under-reports dirt is either a
    per-step upload regression or a stale device table (KV reads through
    wrong pages)."""
    import numpy as np

    PA = mod.PageAllocator
    alloc = PA(num_pages=8, page_size=4, max_slots=4, max_pages_per_slot=4)
    assert not alloc.dirty                       # fresh allocator is clean
    assert alloc.allocate_slot(0, 4)
    assert alloc.dirty                           # allocation dirties its row
    alloc.tables()
    assert not alloc.dirty                       # reading the table cleans

    # growth WITHIN the allocated pages is clean (no upload); crossing a
    # page boundary dirties exactly then
    assert alloc.grow_slot(0, 3) == 4
    assert not alloc.dirty
    assert alloc.grow_slot(0, 5) == 8
    assert alloc.dirty
    row = np.asarray(alloc.tables())[0]
    assert (row[:2] > 0).all() and (row[2:] == 0).all()

    # a cap-bound partial grant persists the pages it DID take
    assert alloc.grow_slot(0, 99) == 16          # capped by max_pages_per_slot
    assert alloc.slot_pages(0) == 4 and alloc.dirty
    alloc.tables()

    # ...and so does a POOL-DRY partial grant (distinct branch: free list
    # exhausted below both the target and the per-slot cap)
    dry = PA(num_pages=4, page_size=4, max_slots=4, max_pages_per_slot=8)
    assert dry.allocate_slot(0, 4) and dry.allocate_slot(1, 4)
    assert dry.grow_slot(0, 12) == 8             # wanted 3 pages, pool had 1
    assert dry.slot_pages(0) == 2 and dry.free_pages == 0
    assert dry.dirty

    # a free dirties; the freed row reads back as zeros
    alloc.free_slot(0)
    assert alloc.dirty
    assert (np.asarray(alloc.tables()) == 0).all()
    assert not alloc.dirty


def _pregrant_block_spec(mod: types.ModuleType) -> None:
    """Super-step pre-grant contract (token-loop fusion): ONE call grants
    a K-token decode block's pages and returns the usable token budget.
    The off-by-one space here — input token at position n_ctx-1, the
    LAST sampled token's KV deferred to the next dispatch — is exactly
    where a silent mutant truncates streams or overruns granted pages."""
    PA = mod.PageAllocator
    alloc = PA(num_pages=8, page_size=4, max_slots=2, max_pages_per_slot=4)
    assert alloc.allocate_slot(0, 4)            # 1 page, capacity 4
    # k=0 is a no-op: zero budget AND zero page-growth side effect
    before = alloc.pages_in_use
    assert alloc.pregrant_block(0, 9, 0) == 0
    assert alloc.pages_in_use == before
    # k=1 at the page edge: capacity n_ctx+k-1 = 4 still fits 1 page
    assert alloc.pregrant_block(0, 4, 1) == 1
    assert alloc.pages_in_use == before
    # crossing the boundary by exactly one token grows exactly one page
    assert alloc.pregrant_block(0, 4, 2) == 2   # needs 5 tokens -> 2 pages
    assert alloc.pages_in_use == before + 1

    # partial grant: wants 3 pages' capacity, the pool has one free page
    dry = PA(num_pages=3, page_size=4, max_slots=2, max_pages_per_slot=4)
    assert dry.allocate_slot(0, 4)              # 1 page; 1 free remains
    assert dry.pregrant_block(0, 6, 4) == 3     # capacity 8: min(4, 8-5)
    # dry pool + slot at its capacity edge: zero budget, never 1/negative
    assert dry.pregrant_block(0, 9, 4) == 0


def _quantize_moe_and_scale_spec(mod: types.ModuleType) -> None:
    """MoE expert-stack quant rules + the embed multiplier knob."""
    import jax.numpy as jnp
    import numpy as np

    # [E, D, F] stack quantizes per (expert, out-channel): axis 1 reduced
    w = np.arange(2 * 3 * 4, dtype=np.float32).reshape(2, 3, 4) - 10.0
    logical = {"w1": "moe_up", "w2": "moe_down", "n": "replicated"}
    tree = {"w1": w, "w2": np.transpose(w, (0, 2, 1)),
            "n": np.ones((3,), np.float32)}
    quant = mod.quantize_tree(tree, logical, scale_dtype=jnp.float32)
    assert quant["w1"]["q"].shape == (2, 3, 4)
    assert quant["w1"]["s"].shape == (2, 4)      # axis 1 reduced
    assert quant["w2"]["s"].shape == (2, 3)
    np.testing.assert_allclose(
        np.asarray(quant["w1"]["s"]),
        np.max(np.abs(w), axis=1) / 127.0, rtol=1e-6)
    # reconstruction error bounded by one quant step per channel
    recon = (np.asarray(quant["w1"]["q"], np.float32)
             * np.asarray(quant["w1"]["s"])[:, None, :])
    assert np.max(np.abs(recon - w)) <= np.max(np.asarray(quant["w1"]["s"]))
    # norms (no rule) stay untouched
    np.testing.assert_array_equal(np.asarray(quant["n"]), tree["n"])

    # embed multiplier: exact scaling, plain AND quantized tables
    table = np.array([[1.0, -2.0], [0.5, 4.0]], np.float32)
    tokens = jnp.asarray([1, 0])
    plain = np.asarray(mod.embed_rows(jnp.asarray(table), tokens, 8.0))
    np.testing.assert_allclose(plain, table[[1, 0]] * 8.0, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mod.embed_rows(jnp.asarray(table), tokens)),
        table[[1, 0]], rtol=1e-6)  # default multiplier is identity
    qtable = mod.quantize_leaf(table, axis=1)
    scaled = np.asarray(mod.embed_rows(qtable, tokens, 8.0))
    unscaled = np.asarray(mod.embed_rows(qtable, tokens))
    np.testing.assert_allclose(scaled, unscaled * 8.0, rtol=1e-6)


# ----------------------------------------------------- avg slot footprint

def _avg_slot_pages_spec(mod: types.ModuleType) -> None:
    a = mod.PageAllocator(num_pages=32, page_size=4, max_slots=4,
                          max_pages_per_slot=8)
    # nothing active: conservative max footprint
    assert a.avg_slot_pages() == 8
    assert a.allocate_slot(0, 8)    # 2 pages
    assert a.avg_slot_pages() == 2
    assert a.allocate_slot(1, 16)   # 4 pages
    assert a.avg_slot_pages() == 3  # (2 + 4) // 2
    a.free_slot(1)
    assert a.avg_slot_pages() == 2
    a.free_slot(0)
    assert a.allocate_slot(2, 2)    # 1 page: floor of the average is 1
    assert a.avg_slot_pages() == 1


def _prefix_tier_spec(mod: types.ModuleType) -> None:
    """Tiered-prefix-cache contract (docs/kv_tiering.md): spill-on-evict
    hands the EXACT chain identity (hash, parent, chunk) to the tier
    client, probe caps tier promises at restore capacity (a probe that
    over-promises livelocks admission), fetch-on-miss restores take one
    reference per page and register locally, failed restores hand the
    page back, and the per-tier hit split conserves against
    prefix_hit_tokens at the same consume site."""
    from mcp_context_forge_tpu.tpu_local.kv.prefix_index import (
        ROOT_HASH, chain_hash, chain_hashes)

    PA = mod.PageAllocator

    class Tiers:
        active = True

        def __init__(self):
            self.keys: set[bytes] = set()
            self.spills: list[tuple] = []
            self.published: list[bytes] = []
            self.unpublished: list[bytes] = []
            self.fail = False

        def probe(self, key_hash):
            return key_hash in self.keys

        def spill(self, key_hash, parent, chunk, page):
            self.spills.append((key_hash, parent, tuple(chunk), page))
            self.keys.add(key_hash)
            return True

        def restore(self, key_hash, parent, chunk, page):
            if self.fail or key_hash not in self.keys:
                return None
            return "host"

        def publish_hbm(self, key_hash):
            self.published.append(key_hash)

        def unpublish_hbm(self, key_hash):
            self.unpublished.append(key_hash)

    tiers = Tiers()
    alloc = PA(num_pages=8, page_size=4, max_slots=4, max_pages_per_slot=4,
               tiers=tiers)
    assert alloc.tier_hits == {"hbm": 0, "host": 0, "disk": 0,
                               "object": 0}
    assert alloc.tier_hit_tokens == {"hbm": 0, "host": 0, "disk": 0,
                                     "object": 0}
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    assert alloc.allocate_slot(0, 9)
    alloc.register_prefix(0, prompt)               # 2 pages + 2 publishes
    assert len(tiers.published) == 2

    # resident consume: the hbm split counts at the SAME site as
    # prefix_hit_tokens (the tenant ledger's cache_hit mirror)
    hist, shared = alloc.match_prefix(prompt)
    assert hist == 8
    assert alloc.allocate_slot(1, 9, prefix_pages=shared)
    assert alloc.tier_hits == {"hbm": 2, "host": 0, "disk": 0,
                               "object": 0}
    assert alloc.tier_hit_tokens == {"hbm": 8, "host": 0, "disk": 0,
                                     "object": 0}
    assert sum(alloc.tier_hit_tokens.values()) == alloc.prefix_hit_tokens
    alloc.free_slot(1)
    alloc.free_slot(0)

    # spill-on-evict: pressure reclaims the (now ref==0) registered
    # pages; each handoff carries the exact chain identity and retracts
    # the HBM publication
    for slot in range(3):
        assert alloc.allocate_slot(slot, 8)
    assert alloc.allocate_slot(3, 4)
    assert len(tiers.spills) >= 2
    by_chunk = {s[2]: s for s in tiers.spills}    # eviction order is
    s0 = by_chunk[(1, 2, 3, 4)]                   # LRU-by-last-match,
    s1 = by_chunk[(5, 6, 7, 8)]                   # not chain order
    assert s0[1] == ROOT_HASH
    assert s0[0] == chain_hash(ROOT_HASH, (1, 2, 3, 4))
    assert s1[1] == s0[0]                         # chained parent
    assert s1[0] == chain_hash(s0[0], (5, 6, 7, 8))
    assert s0[0] in tiers.unpublished and s1[0] in tiers.unpublished

    # fetch-on-miss: a FRESH allocator (same shared tiers) serves the
    # chain from the tier store — probe promises it, match restores it
    # with exactly one reference per page, and the split says "host"
    alloc2 = PA(num_pages=8, page_size=4, max_slots=2, max_pages_per_slot=4,
                tiers=tiers)
    assert alloc2.probe_prefix(prompt) == 8
    assert alloc2.probe_prefix([1, 2, 3, 4]) == 0  # last token never matches
    hist, pages2 = alloc2.match_prefix(prompt)
    assert hist == 8 and len(pages2) == 2
    assert all(alloc2._ref[p] == 1 for p in pages2)
    assert alloc2.allocate_slot(0, 9, prefix_pages=pages2)
    assert alloc2.tier_hits == {"hbm": 0, "host": 2, "disk": 0,
                                "object": 0}
    assert alloc2.tier_hit_tokens["host"] == 8
    assert sum(alloc2.tier_hit_tokens.values()) == alloc2.prefix_hit_tokens
    alloc2.free_slot(0)
    # restored pages registered locally: the re-match is resident (hbm),
    # and re-referencing an LRU page starts its count at exactly one
    assert alloc2.probe_prefix(prompt) == 8
    hist, pages3 = alloc2.match_prefix(prompt)
    assert hist == 8
    assert all(alloc2._ref[p] == 1 for p in pages3)
    assert alloc2.allocate_slot(1, 9, prefix_pages=pages3)
    assert alloc2.tier_hits["hbm"] == 2
    alloc2.free_slot(1)

    # spill-on-drain (docs/resilience.md): EVERY ref==0 registered page
    # spills with its exact chain identity, the count is exact, pinned
    # spans never spill, and a page missing its hash evidence is
    # SKIPPED (never unpacked) — tier-less/inactive allocators return
    # exactly 0
    spills_before = len(tiers.spills)
    assert alloc2.spill_resident_prefix() == 2
    assert len(tiers.spills) == spills_before + 2
    assert {s[2] for s in tiers.spills[-2:]} == {(1, 2, 3, 4),
                                                 (5, 6, 7, 8)}
    page = next(iter(alloc2._lru))
    saved = alloc2._page_hash.pop(page)            # defensive-skip branch
    assert alloc2.spill_resident_prefix() == 1
    alloc2._page_hash[page] = saved
    hist, pages4 = alloc2.match_prefix(prompt)     # pin both pages
    assert hist == 8 and alloc2.allocate_slot(0, 9, prefix_pages=pages4)
    assert alloc2.spill_resident_prefix() == 0     # in-flight: untouched
    alloc2.free_slot(0)
    assert PA(num_pages=8, page_size=4, max_slots=2,
              max_pages_per_slot=4).spill_resident_prefix() == 0
    tiers.active = False
    assert alloc2.spill_resident_prefix() == 0
    tiers.active = True

    # migration export (docs/disaggregation.md): spill_chain walks the
    # prompt's registered FULL pages in chain order with exact identity,
    # COPY semantics (pages stay resident and matchable), includes the
    # final page of an exact-boundary prompt (the continuation prompt's
    # matchable depth), stops at the first unregistered depth, and
    # tier-less/inactive allocators return exactly 0
    spills_before = len(tiers.spills)
    assert alloc2.spill_chain(prompt) == 2         # exact count
    assert len(tiers.spills) == spills_before + 2
    assert [s[2] for s in tiers.spills[-2:]] == [(1, 2, 3, 4),
                                                 (5, 6, 7, 8)]  # chain order
    assert tiers.spills[-2][0] == chain_hash(ROOT_HASH, (1, 2, 3, 4))
    assert tiers.spills[-2][1] == ROOT_HASH        # exact identity
    assert tiers.spills[-1][1] == tiers.spills[-2][0]
    assert alloc2.probe_prefix(prompt) == 8        # copy: still resident
    assert alloc2.spill_chain(prompt[:8]) == 2     # exact page boundary
    assert alloc2.spill_chain([90, 91, 92, 93]) == 0   # unregistered chain
    assert alloc2.spill_chain(prompt[:3]) == 0     # no full page to walk
    assert PA(num_pages=8, page_size=4, max_slots=2,
              max_pages_per_slot=4).spill_chain(prompt) == 0   # tier-less
    tiers.active = False
    assert alloc2.spill_chain(prompt) == 0
    tiers.active = True

    # probe caps tier promises at restore capacity: free+evictable of 2
    # limits a 3-chunk tiered chain to 2 pages; a fully-pinned pool
    # promises nothing (an over-promise here is an admission livelock)
    prompt13 = list(range(20, 33))                 # 3 full pages + 1 token
    tiers.keys.update(chain_hashes(prompt13, 4))
    alloc3 = PA(num_pages=6, page_size=4, max_slots=2, max_pages_per_slot=4,
                tiers=tiers)                       # 5 usable
    assert alloc3.allocate_slot(0, 12)             # 3 pinned -> capacity 2
    assert alloc3.probe_prefix(prompt13) == 8
    alloc4 = PA(num_pages=4, page_size=4, max_slots=2, max_pages_per_slot=4,
                tiers=tiers)                       # 3 usable
    assert alloc4.allocate_slot(0, 12)             # everything pinned
    assert alloc4.probe_prefix(prompt13) == 0

    # matching a resident ref==0 (LRU) chain page PINS it, consuming one
    # unit of the capacity later restores draw from — the probe must
    # model that or it promises a hist match_prefix cannot deliver
    # (admission livelock). A ref>0 resident page consumes nothing.
    alloc6 = PA(num_pages=5, page_size=4, max_slots=3, max_pages_per_slot=4,
                tiers=tiers)                       # 4 usable
    assert alloc6.allocate_slot(0, 5)              # 2 pages
    alloc6.register_prefix(0, prompt13[:5])        # chunk0 resident
    alloc6.free_slot(0)                            # chunk0 -> LRU
    assert alloc6.allocate_slot(1, 8)              # pin two free pages
    assert alloc6.free_pages == 2                  # 1 free + 1 evictable
    # chunk0 local-LRU (consumes 1) + chunk1 from tier (consumes 1);
    # chunk2 finds no capacity left
    assert alloc6.probe_prefix(prompt13) == 8
    hist, pages6 = alloc6.match_prefix(prompt13[:5])
    assert hist == 4
    assert alloc6.allocate_slot(2, 5, prefix_pages=pages6)  # chunk0 ref>0
    alloc6.free_slot(1)                            # capacity back: 2 free
    assert alloc6.free_pages == 2
    # the PINNED chunk0 consumes NO capacity: both tier chunks fit it
    assert alloc6.probe_prefix(prompt13) == 12

    # registration covers the FINAL page of an exact-multiple prompt
    # (matches never cover the last token, but longer prompts share it)
    exact = PA(num_pages=8, page_size=4, max_slots=2, max_pages_per_slot=4)
    assert exact.allocate_slot(0, 8)
    exact.register_prefix(0, [11, 12, 13, 14, 15, 16, 17, 18])
    assert exact.cached_pages == 2
    hist, m = exact.match_prefix([11, 12, 13, 14, 15, 16, 17, 18, 90, 91])
    assert hist == 8
    exact.release_prefix(m)
    # ...and registering a prompt LONGER than the slot's pages stops at
    # the pages the slot actually holds
    assert exact.allocate_slot(1, 4)               # 1 page
    exact.register_prefix(1, list(range(40, 52)))  # 3 full chunks
    assert exact.cached_pages == 3                 # 2 from slot 0 + 1 new

    # failed restore: the taken page goes BACK (no leak) and the match
    # ends at the pages already secured
    tiers.fail = True
    free_before = alloc3.free_pages
    hist, pages4 = alloc3.match_prefix(prompt13)
    assert hist == 0 and pages4 == []
    assert alloc3.free_pages == free_before
    tiers.fail = False

    # ...and a fully-pinned MATCH stops cleanly at zero (a mutant that
    # reads the capacity guard wrong walks into _take_page's trap)
    hist, none = alloc4.match_prefix(prompt13)
    assert hist == 0 and none == []

    # a TIER-LESS allocator's match breaks at the first uncached chunk
    # even with free pages in hand (the tier walk must be unreachable
    # without a client — reaching it here is an attribute error)
    plain = PA(num_pages=8, page_size=4, max_slots=2, max_pages_per_slot=4)
    assert plain.allocate_slot(0, 9)
    plain.register_prefix(0, prompt)
    hist, partial = plain.match_prefix([1, 2, 3, 4, 90, 91, 92, 93, 94])
    assert hist == 4 and len(partial) == 1
    plain.release_prefix(partial)

    # first registration of a chain key WINS: a later identical prompt's
    # pages stay private (a mutant that re-registers would swap the
    # cached chain onto the newer slot's pages)
    first_pages = list(plain._slots[0][:2])
    assert plain.allocate_slot(1, 9)
    plain.register_prefix(1, prompt)
    hist, m = plain.match_prefix(prompt)
    assert hist == 8 and m == first_pages
    plain.release_prefix(m)

    # the empty-pool bug trap: _take_page with nothing free and nothing
    # evictable must raise, not hand out a phantom page
    boom = PA(num_pages=2, page_size=4, max_slots=1, max_pages_per_slot=4,
              tiers=tiers)
    assert boom.allocate_slot(0, 4)                # the only usable page
    try:
        boom._take_page()
        raise AssertionError("exhausted pool handed out a phantom page")
    except RuntimeError:
        pass


# ----------------------------------------------------------- fabric index

def _fabric_index_spec(mod: types.ModuleType) -> None:
    """Behavioral spec of the cross-host fabric index
    (docs/cache_fabric.md): advert merge is monotone and counts only
    NEW hashes, TTL expiry is the only eviction (lazy on covers + eager
    sweep), tenant namespaces never cross, origin attribution is
    first-registration-wins, and the wire codec round-trips / rejects
    malformed frames. A surviving mutant here means a host promising
    cross-host restores it cannot deliver (admission livelock) or one
    tenant's cached pages visible to another."""
    clock = [1000.0]
    idx = mod.FabricIndex(default_ttl_s=10.0, clock=lambda: clock[0])
    h1, h2, h3 = b"\x01" * 32, b"\x02" * 32, b"\x03" * 32

    # merge counts NEW hashes only; covers/lookup agree
    assert idx.merge(mod.FabricAdvert(tenant="t", host="A",
                                      hashes=[h1, h2])) == 2
    assert idx.merge(mod.FabricAdvert(tenant="t", host="A",
                                      hashes=[h1, h3])) == 1
    assert idx.covers(h1, "t") and idx.covers(h3, "t")
    assert idx.lookup(h1, "t") == "A"
    assert idx.stats()["keys"] == 3

    # tenant isolation: the SAME hash under another namespace is a miss
    assert not idx.covers(h1, "other")
    assert idx.lookup(h1, "other") is None
    assert idx.hashes("other") == []
    idx.invalidate(h1, "other")                    # wrong tenant: no-op
    assert idx.covers(h1, "t")

    # first-registration-wins: a re-advert from another host refreshes
    # the expiry but never reassigns the origin
    clock[0] = 1005.0
    assert idx.merge(mod.FabricAdvert(tenant="t", host="B",
                                      hashes=[h1])) == 0
    assert idx.lookup(h1, "t") == "A"

    # ...and the refresh only EXTENDS: an advert with a shorter ttl
    # cannot pull an existing expiry earlier
    idx.merge(mod.FabricAdvert(tenant="t", host="B", hashes=[h1],
                               ttl_s=0.5))
    clock[0] = 1011.0                              # h2/h3 (exp 1010) dead
    assert idx.covers(h1, "t")                     # refreshed to 1015
    assert not idx.covers(h2, "t")                 # lazy expiry on read
    assert idx.sweep() == 1                        # h3 swept eagerly
    assert idx.stats()["keys"] == 1

    # invalidate drops exactly the (tenant, hash) entry
    idx.invalidate(h1, "t")
    assert not idx.covers(h1, "t")
    assert idx.lookup(h1, "t") is None
    assert idx.invalidated == 1

    # expiry is the ONLY eviction a merge can never perform: re-merging
    # after expiry counts as NEW again (monotone within a lifetime)
    assert idx.merge(mod.FabricAdvert(tenant="t", host="C",
                                      hashes=[h2])) == 1
    assert idx.lookup(h2, "t") == "C"              # fresh registration

    # wire codec: round trip exact; malformed frames raise ValueError
    advert = mod.FabricAdvert(tenant="t", host="A", hashes=[h1],
                              ttl_s=5.0)
    assert mod.FabricAdvert.from_wire(advert.to_wire()) == advert
    for bad in ("nope", {"tenant": "t"}, {"tenant": "t", "host": ""},
                {"tenant": "t", "host": "A", "hashes": ["zz"]},
                {"tenant": "t", "host": "A", "hashes": ["abcd"]}):
        try:
            mod.FabricAdvert.from_wire(bad)
            raise AssertionError(f"malformed advert accepted: {bad!r}")
        except ValueError:
            pass
    # oversize adverts truncate at the wire boundary, never reject
    digest_hex = (b"\x07" * 32).hex()
    big = {"tenant": "t", "host": "A",
           "hashes": [digest_hex] * (mod.MAX_ADVERT_HASHES + 5)}
    assert len(mod.FabricAdvert.from_wire(big).hashes) \
        == mod.MAX_ADVERT_HASHES
    fresh = mod.FabricIndex(default_ttl_s=10.0, clock=lambda: clock[0])
    assert mod.merge_wire_adverts(fresh, [advert.to_wire()]) == 1
    assert fresh.covers(h1, "t")

    # the re-advertisable view groups by tenant and relabels the relay
    fresh.merge(mod.FabricAdvert(tenant="u", host="B", hashes=[h2]))
    out = fresh.adverts("relay")
    assert [(a.tenant, a.host, a.hashes) for a in out] \
        == [("t", "relay", [h1]), ("u", "relay", [h2])]

    # counters start at zero and count by exactly one — no-ops (a
    # wrong-tenant invalidate) are NOT counted
    z = mod.FabricIndex(default_ttl_s=10.0, clock=lambda: clock[0])
    assert (z.merged, z.refreshed, z.expired, z.invalidated) \
        == (0, 0, 0, 0)
    z.merge(mod.FabricAdvert(tenant="t", host="A", hashes=[h1]))
    assert z.merged == 1 and z.refreshed == 0
    z.merge(mod.FabricAdvert(tenant="t", host="A", hashes=[h1]))
    assert z.merged == 1 and z.refreshed == 1
    z.invalidate(h1, "nope")
    assert z.invalidated == 0
    z.invalidate(h1, "t")
    assert z.invalidated == 1

    # an explicit positive ttl REPLACES the default (shorter is legal
    # for a fresh entry): a 0.5 s advert on a 10 s-default index is
    # gone at +1 s
    clock[0] = 2000.0
    z.merge(mod.FabricAdvert(tenant="t", host="A", hashes=[h2],
                             ttl_s=0.5))
    clock[0] = 2001.0
    assert not z.covers(h2, "t")

    # the expiry boundary is EXACT: at expires_at == now the entry is
    # dead on EVERY read path, and each lazy expiry counts once
    b = mod.FabricIndex(default_ttl_s=10.0, clock=lambda: clock[0])
    clock[0] = 3000.0
    b.merge(mod.FabricAdvert(tenant="t", host="A", hashes=[h1, h2]))
    clock[0] = 3010.0                              # == expires_at
    assert b.stats()["keys"] == 0
    assert b.stats()["hosts"] == [] and b.stats()["tenants"] == []
    assert b.hashes("t") == [] and b.adverts("r") == []
    assert b.lookup(h1, "t") is None
    assert not b.covers(h1, "t")                   # lazy-expires h1
    assert b.expired == 1
    assert b.sweep() == 1                          # h2, at the boundary
    assert b.expired == 2


# ------------------------------------------------------------ eventstream

def eventstream_oracle(mod: types.ModuleType) -> None:
    """Behavioral spec of the AWS event-stream codec: exact framing
    layout, both CRCs live, typed headers, incremental reassembly. A
    surviving mutant means silently corrupt Bedrock streams."""
    import asyncio
    import zlib

    headers = {":event-type": "contentBlockDelta", ":message-type": "event"}
    payload = b'{"delta":{"text":"hi"}}'
    frame = mod.encode_frame(headers, payload)
    # exact layout: total length, headers length, prelude CRC
    total = int.from_bytes(frame[0:4], "big")
    assert total == len(frame)
    hlen = int.from_bytes(frame[4:8], "big")
    assert hlen == len(frame) - 12 - 4 - len(payload)
    assert int.from_bytes(frame[8:12], "big") == zlib.crc32(frame[0:8])
    assert int.from_bytes(frame[-4:], "big") == zlib.crc32(frame[:-4])
    assert mod.decode_frame(frame) == (headers, payload)
    assert mod.decode_frame(mod.encode_frame({}, b"")) == ({}, b"")

    # every corrupted byte position must be caught by SOME check
    for pos in (2, 5, 9, 13, len(frame) - 6, len(frame) - 2):
        corrupt = bytearray(frame)
        corrupt[pos] ^= 0xFF
        try:
            mod.decode_frame(bytes(corrupt))
        except mod.EventStreamError:
            pass
        else:
            raise AssertionError(f"corruption at byte {pos} accepted")

    # typed headers: bool true/false + every scalar width + bytes + string
    hdr = bytes([1]) + b"t" + bytes([0])
    hdr += bytes([1]) + b"f" + bytes([1])
    hdr += bytes([1]) + b"a" + bytes([2]) + (5).to_bytes(1, "big")
    hdr += bytes([1]) + b"b" + bytes([3]) + (-300).to_bytes(2, "big",
                                                            signed=True)
    hdr += bytes([1]) + b"c" + bytes([4]) + (7).to_bytes(4, "big")
    hdr += bytes([1]) + b"d" + bytes([5]) + (2**40).to_bytes(8, "big")
    hdr += bytes([1]) + b"e" + bytes([8]) + (123456).to_bytes(8, "big")
    hdr += bytes([1]) + b"u" + bytes([9]) + bytes(range(16))
    hdr += bytes([1]) + b"s" + bytes([7]) + (2).to_bytes(2, "big") + b"ok"
    hdr += bytes([1]) + b"r" + bytes([6]) + (3).to_bytes(2, "big") + b"\x01\x02\x03"
    parsed = mod._parse_headers(hdr)
    assert parsed == {"t": True, "f": False, "a": 5, "b": -300, "c": 7,
                      "d": 2**40, "e": 123456, "u": bytes(range(16)),
                      "s": "ok", "r": b"\x01\x02\x03"}
    # unknown value type is an error, not silent garbage
    try:
        mod._parse_headers(bytes([1]) + b"x" + bytes([99]))
    except mod.EventStreamError:
        pass
    else:
        raise AssertionError("unknown header type accepted")

    # bad prelude CRC with a RECOMPUTED (valid) message CRC: only the
    # prelude check can catch this one
    broken = bytearray(frame)
    broken[8] ^= 0xFF
    broken[-4:] = zlib.crc32(bytes(broken[:-4])).to_bytes(4, "big")
    try:
        mod.decode_frame(bytes(broken))
    except mod.EventStreamError as exc:
        assert "prelude" in str(exc)
    else:
        raise AssertionError("bad prelude CRC accepted")

    # extra bytes past the claimed total, with the TRAILING CRC recomputed
    # so both CRC checks pass: only the length check can catch this
    padded = bytearray(frame + b"\x00" * 6)
    padded[-4:] = zlib.crc32(bytes(padded[:-4])).to_bytes(4, "big")
    try:
        mod.decode_frame(bytes(padded))
    except mod.EventStreamError as exc:
        assert "length" in str(exc)
    else:
        raise AssertionError("over-long frame accepted")

    # incremental reassembly across every split granularity
    frames = [mod.encode_frame({"k": str(i)}, bytes([i]) * i)
              for i in range(5)]
    frames.append(mod.encode_frame({}, b""))   # the minimal 16-byte frame
    blob = b"".join(frames)

    async def collect(step):
        async def chunks():
            for i in range(0, len(blob), step):
                yield blob[i:i + step]
        return [h async for h, _ in mod.iter_frames(chunks())]

    for step in (1, 3, len(blob)):
        got = asyncio.run(collect(step))
        assert [h.get("k") for h in got] == ["0", "1", "2", "3", "4", None]

    async def feed(data):
        async def chunks():
            yield data
        return [f async for f in mod.iter_frames(chunks())]

    try:
        asyncio.run(feed(blob + b"\x00"))
    except mod.EventStreamError:
        pass
    else:
        raise AssertionError("trailing bytes accepted")
    # implausible frame lengths fail fast instead of buffering forever
    for claimed in (3, 17 * 1024 * 1024):
        bad = claimed.to_bytes(4, "big") + b"\x00" * 12
        try:
            asyncio.run(feed(bad))
        except mod.EventStreamError:
            pass
        else:
            raise AssertionError(f"implausible length {claimed} accepted")
    # a stream that ENDS mid-frame is an error (incomplete trailing frame)
    try:
        asyncio.run(feed(frame[:11]))
    except mod.EventStreamError:
        pass
    else:
        raise AssertionError("truncated stream accepted")


# ------------------------------------------------------------- tool_calls

def tool_calls_oracle(mod: types.ModuleType) -> None:
    """Behavioral spec of the function-calling wire layer: accepted
    emission shapes, rejection of plain answers, OpenAI tool_calls
    structure, render/parse round trip."""
    import json as _json

    calls = mod.parse_tool_calls('{"name": "f", "parameters": {"a": 1}}')
    assert len(calls) == 1
    call = calls[0]
    assert call["type"] == "function"
    assert call["id"].startswith("call_")
    assert call["function"]["name"] == "f"
    assert _json.loads(call["function"]["arguments"]) == {"a": 1}

    # alternate key spellings
    assert mod.parse_tool_calls(
        '{"name": "g", "arguments": {"x": 2}}')[0]["function"]["name"] == "g"
    assert mod.parse_tool_calls(
        '{"tool": "h", "arguments": {}}')[0]["function"]["name"] == "h"
    # arrays = parallel calls, order preserved, unique ids
    multi = mod.parse_tool_calls(
        '[{"name": "a", "parameters": {}}, {"name": "b", "parameters": {}}]')
    assert [c["function"]["name"] for c in multi] == ["a", "b"]
    assert multi[0]["id"] != multi[1]["id"]
    # python_tag prefix and prose wrapping
    assert mod.parse_tool_calls(
        '<|python_tag|>{"name": "f", "parameters": {}}') is not None
    assert mod.parse_tool_calls(
        'Sure.\n{"name": "f", "parameters": {}}\nDone.') is not None
    # rejections: plain text, missing/empty name, scalar args, non-dicts
    for bad in ("plain answer", '{"x": 1}', '{"name": "", "parameters": {}}',
                '{"name": "f", "parameters": 3}', "[1, 2]", "[]",
                '[{"name": "f", "parameters": {}}, {"x": 1}]'):
        assert mod.parse_tool_calls(bad) is None, bad

    # non-string names reject; id carries 16 hex chars after the prefix
    assert mod.parse_tool_calls('{"name": 3, "parameters": {}}') is None
    assert len(call["id"]) == len("call_") + 16
    # leading-JSON-with-trailing-prose parses via the outermost span
    tail = mod.parse_tool_calls('{"name": "t", "parameters": {}}thanks!')
    assert tail[0]["function"]["name"] == "t"

    # render block lists every signature + the call instruction,
    # INCLUDING the parameters schema
    block = mod.render_tools_block([
        {"type": "function", "function": {"name": "fn1", "description": "D",
                                          "parameters": {"type": "object"}}}])
    assert "fn1" in block and "D" in block
    assert '{"type":"object"}' in block
    assert '"<function-name>"' in block

    # round trip: rendered call text re-parses to the same call
    text = mod.tool_call_message_text(calls)
    again = mod.parse_tool_calls(text)
    assert again[0]["function"]["name"] == "f"
    assert _json.loads(again[0]["function"]["arguments"]) == {"a": 1}
    multi_text = mod.tool_call_message_text(multi)
    assert [c["function"]["name"] for c in mod.parse_tool_calls(multi_text)] \
        == ["a", "b"]


# ------------------------------------------------------------- lint engine

def lint_core_oracle(mod: types.ModuleType) -> None:
    """Behavioral spec of tools/lint/core.py: marker parsing from real
    comments only, per-line suppression, content-anchored baseline
    match/consume/stale, registry invariants, and finding triage. A
    surviving mutant is a linter that silently eats findings — the gate
    stays green while the hazard ships."""
    import json as _json
    import tempfile
    import types as _types
    from pathlib import Path as _Path

    # ---- Finding shape
    f = mod.Finding("r1", "a.py", 3, "msg", code="xx")
    assert str(f) == "a.py:3: r1 msg"
    assert f.to_dict() == {"rule": "r1", "path": "a.py", "lineno": 3,
                           "message": "msg", "code": "xx"}

    # ---- FileContext: markers from real comments, line-keyed
    src = ("first = 1  # lint: allow[rule-a] reason\n"
           "second = 2  # lint: thread[dispatch]\n"
           "s = '# lint: allow[rule-b]'\n"
           "def fn(a,\n"
           "       b):  # lint: hot-path\n"
           "    pass  # lint: runs-on[loop]\n"
           "# lint: allow[rule-c] # lint: allow[rule-d]\n"
           "def one(): pass  # lint: hot-path\n"
           "after = 3  # lint: runs-on[next]\n")
    ctx = mod.FileContext.from_source(src, "m.py")
    assert ctx.path == "m.py"
    assert ctx.allowed(1) == {"rule-a"}
    assert ctx.allowed(2) == set()         # thread marker is not allow
    assert ctx.allowed(3) == set()         # string literal never counts
    assert ctx.allowed(7) == {"rule-c", "rule-d"}
    assert ctx.markers_of("thread") == {2: "dispatch"}
    assert ctx.markers_of("hot-path") == {5: "", 8: ""}
    assert ctx.markers_of("runs-on") == {6: "loop", 9: "next"}
    assert ctx.markers_of("nope") == {}
    assert ctx.line(1) == "first = 1  # lint: allow[rule-a] reason"
    assert ctx.line(7) == "# lint: allow[rule-c] # lint: allow[rule-d]"
    assert ctx.line(0) == "" and ctx.line(99) == ""

    # def_marker: anywhere in the (multi-line) signature counts, the
    # body does not
    fndef = ctx.tree.body[3]
    assert mod.FileContext.def_marker(ctx, fndef, "hot-path") == ""
    assert mod.FileContext.def_marker(ctx, fndef, "runs-on") is None
    # a ONE-LINE def counts its only line — and ONLY that line (the
    # runs-on marker on line 9 belongs to the next statement)
    onedef = ctx.tree.body[4]
    assert onedef.lineno == onedef.body[0].lineno == 8
    assert mod.FileContext.def_marker(ctx, onedef, "hot-path") == ""
    assert mod.FileContext.def_marker(ctx, onedef, "runs-on") is None
    # body-less node: the one-line fallback window
    probe = _types.SimpleNamespace(lineno=1, body=[])
    assert ctx.def_marker(probe, "allow") == "rule-a"
    probe = _types.SimpleNamespace(lineno=1, body=None)
    assert ctx.def_marker(probe, "thread") is None  # line 2 is outside

    # ---- Rule base + registry
    base = mod.Rule()
    assert list(base.check(ctx)) == []
    assert list(base.check_project([ctx])) == []
    assert list(base.check_graph(None, [ctx])) == []

    class ROne(mod.Rule):
        rule_id = "r-one"

    mod.register(ROne)
    assert mod.registered_rules()["r-one"] is ROne
    try:
        mod.register(ROne)
    except ValueError:
        pass
    else:
        raise AssertionError("duplicate rule id accepted")

    class RNone(mod.Rule):
        pass

    try:
        mod.register(RNone)
    except ValueError:
        pass
    else:
        raise AssertionError("empty rule id accepted")

    # ---- path identity across invocation styles: exact or whole-segment
    # suffix, both directions; never a partial-segment match
    assert mod.paths_match("a/b.py", "a/b.py") is True
    assert mod.paths_match("/root/repo/pkg/b.py", "pkg/b.py") is True
    assert mod.paths_match("pkg/b.py", "/root/repo/pkg/b.py") is True
    assert mod.paths_match("my.py", "y.py") is False
    assert mod.paths_match("a/b.py", "a/c.py") is False

    # ---- Baseline: content-anchored match, consume-once, stale report
    entry = {"rule": "fire", "path": "a.py", "code": "BAD = 2",
             "reason": "known"}
    other = {"rule": "fire", "path": "b.py", "code": "BAD = 9",
             "reason": "known"}
    hit = mod.Finding("fire", "a.py", 2, "m", code="BAD = 2")
    baseline = mod.Baseline(entries=[entry, other])
    assert baseline.match(hit) is True
    assert baseline.match(hit) is False      # consumed: match exactly once
    assert baseline.stale() == [other]
    # every anchor field is load-bearing
    for wrong in (mod.Finding("other", "a.py", 2, "m", code="BAD = 2"),
                  mod.Finding("fire", "z.py", 2, "m", code="BAD = 2"),
                  mod.Finding("fire", "a.py", 2, "m", code="OTHER")):
        assert mod.Baseline(entries=[entry]).match(wrong) is False
    # a relative entry suppresses the absolute spelling of the same file
    absolute = mod.Finding("fire", "/root/repo/a.py", 2, "m", code="BAD = 2")
    assert mod.Baseline(entries=[entry]).match(absolute) is True
    assert mod.Baseline.entry_for(hit, "why") == {
        "rule": "fire", "path": "a.py", "code": "BAD = 2", "reason": "why"}

    with tempfile.TemporaryDirectory() as tmp:
        path = _Path(tmp) / "baseline.json"
        mod.Baseline(entries=[entry]).save(path)
        assert path.read_text() == _json.dumps(
            {"entries": [entry]}, indent=2, sort_keys=True) + "\n"
        assert mod.Baseline.load(path).entries == [entry]
        assert mod.Baseline.load(path).stale() == [entry]  # fresh _used
        try:
            mod.Baseline(entries=[{"rule": "x", "path": "y",
                                   "code": "z"}]).save(path)
        except ValueError:
            pass
        else:
            raise AssertionError("reason-less baseline entry saved")
        # ...and load refuses it too: a hand-added reason-less entry
        # must not silently suppress
        path.write_text(_json.dumps(
            {"entries": [{"rule": "x", "path": "y", "code": "z"}]}))
        try:
            mod.Baseline.load(path)
        except ValueError:
            pass
        else:
            raise AssertionError("reason-less baseline entry loaded")
        # the gate-side load also refuses --write-baseline's TODO
        # placeholder, while save accepts it (the authoring flow writes
        # placeholders for the maintainer to replace)
        todo = {"rule": "x", "path": "y", "code": "z",
                "reason": "TODO: justify or fix"}
        mod.Baseline(entries=[todo]).save(path)      # authoring: ok
        assert _json.loads(path.read_text())["entries"] == [todo]
        try:
            mod.Baseline.load(path)
        except ValueError:
            pass
        else:
            raise AssertionError("TODO placeholder reason loaded")
        real = dict(todo, reason="legacy client; migrating")
        path.write_text(_json.dumps({"entries": [real]}))
        assert mod.Baseline.load(path).entries == [real]

    # ---- LintResult.clean
    ok = mod.Finding("r", "p", 1, "m")
    assert mod.LintResult().clean is True
    assert mod.LintResult(findings=[ok]).clean is False
    assert mod.LintResult(errors=[ok]).clean is False

    # ---- triage pipeline: fire / suppress / baseline / project / sort
    class Fire(mod.Rule):
        rule_id = "fire"

        def check(self, c):
            for i, line in enumerate(c.lines, start=1):
                if "BAD" in line:
                    yield mod.Finding("fire", c.path, i, "bad thing")

    class Proj(mod.Rule):
        rule_id = "proj"

        def check_project(self, cs):
            if len(cs) >= 2:
                yield mod.Finding("proj", cs[0].path, 1, "pair",
                                  code="anchored")
            yield mod.Finding("proj", "outside.py", 5, "external")

    rules = [Fire(), Proj()]
    res = mod.lint_sources({"a.py": "ok = 1\nBAD = 2\n"}, [Fire()])
    assert [f.lineno for f in res.findings] == [2]
    assert res.findings[0].code == "BAD = 2"   # code filled from source
    assert res.clean is False and res.suppressed == [] \
        and res.baselined == [] and res.stale_baseline == []

    res = mod.lint_sources(
        {"a.py": "BAD = 2  # lint: allow[fire] migrating\n"}, [Fire()])
    assert res.findings == [] and len(res.suppressed) == 1
    res = mod.lint_sources(
        {"a.py": "BAD = 2  # lint: allow[other]\n"}, [Fire()])
    assert len(res.findings) == 1              # wrong rule id still fires

    res = mod.lint_sources(
        {"a.py": "BAD = 2\n"}, [Fire()],
        mod.Baseline(entries=[dict(entry), dict(other)]))
    assert res.findings == [] and len(res.baselined) == 1
    assert res.stale_baseline == [other]

    # two files: per-file + project findings, sorted by (path, lineno);
    # a finding for a path outside the context set passes through with
    # its own code anchor intact
    res = mod.lint_sources({"a.py": "ok = 3\n", "b.py": "x = 1\nBAD = 2\n"},
                           rules)
    assert [(f.path, f.lineno, f.rule) for f in res.findings] == [
        ("a.py", 1, "proj"), ("b.py", 2, "fire"), ("outside.py", 5, "proj")]
    assert res.findings[0].code == "anchored"  # pre-set code not clobbered

    # syntax errors are findings, not crashes, and poison cleanliness
    res = mod.lint_sources({"bad.py": "def broken(:\n", "ok.py": "x = 1\n"},
                           [Fire()])
    assert res.clean is False
    assert [e.rule for e in res.errors] == ["syntax-error"]
    assert res.errors[0].path == "bad.py" and res.errors[0].lineno == 1

    # ---- check_graph dispatch: rules that OVERRIDE check_graph get one
    # shared ProjectGraph + the full context list; base-Rule instances
    # must not trigger a build or receive a call
    seen_graphs: list = []
    seen_paths: list = []

    class Graphy(mod.Rule):
        rule_id = "graphy"

        def check_graph(self, graph, contexts):
            seen_graphs.append(graph)
            seen_paths.append([c.path for c in contexts])
            for name in sorted(graph.signal_published):
                if name not in graph.signal_read:
                    site = graph.signal_published[name][0]
                    yield mod.Finding("graphy", site.path, site.lineno,
                                      f"unread {name}")

    class Graphy2(mod.Rule):
        rule_id = "graphy2"

        def check_graph(self, graph, contexts):
            seen_graphs.append(graph)
            return ()

    graph_srcs = {
        "r.py": ('def f(bus):\n'
                 '    bus.publish("a.read", 1.0)\n'
                 '    bus.publish("a.orphan", 1.0)\n'),
        "s.py": 'def g(bus, rid):\n    return bus.get("a.read", rid)\n',
    }
    res = mod.lint_sources(graph_srcs, [Graphy(), Graphy2(), mod.Rule()])
    assert [(f.path, f.lineno, f.message) for f in res.findings] == [
        ("r.py", 3, "unread a.orphan")]
    assert len(seen_graphs) == 2
    assert seen_graphs[0] is seen_graphs[1]    # built ONCE, shared
    assert seen_paths[0] == ["r.py", "s.py"]   # full context list handed in
    # graph findings flow through the same triage: allow[] suppresses
    res = mod.lint_sources(
        {"r.py": ('def f(bus):\n'
                  '    bus.publish("a.orphan", 1.0)'
                  '  # lint: allow[graphy] dashboard-only\n')},
        [Graphy()])
    assert res.findings == [] and len(res.suppressed) == 1

    # ---- triage() direct: the runner calls it with pre-gathered raw
    # findings — code backfill, allow, baseline, sort, stale must all
    # behave exactly as the serial path
    tctx = mod.FileContext.from_source(
        "keep = 1\nBAD = 2  # lint: allow[fire] migrating\n", "t.py")
    raw = [mod.Finding("fire", "t.py", 2, "allowed here"),
           mod.Finding("fire", "t.py", 1, "plain"),
           mod.Finding("fire", "a.py", 2, "baselined", code="BAD = 2"),
           mod.Finding("zz", "no-ctx.py", 9, "passthrough", code="kept")]
    tri = mod.triage([tctx], raw, mod.Baseline(entries=[dict(entry)]))
    assert [(f.path, f.lineno, f.rule) for f in tri.findings] == [
        ("no-ctx.py", 9, "zz"), ("t.py", 1, "fire")]
    assert tri.findings[1].code == "keep = 1"      # backfilled from ctx
    assert tri.findings[0].code == "kept"          # pre-set survives
    assert [f.message for f in tri.suppressed] == ["allowed here"]
    assert [f.message for f in tri.baselined] == ["baselined"]
    assert tri.stale_baseline == []
    assert mod.triage([], [], None).clean is True  # default empty baseline

    # ---- collect_sources: dirs recurse, __pycache__ skipped, files ok
    with tempfile.TemporaryDirectory() as tmp:
        root = _Path(tmp)
        (root / "pkg" / "sub").mkdir(parents=True)
        (root / "pkg" / "__pycache__").mkdir()
        (root / "pkg" / "a.py").write_text("a = 1\n")
        (root / "pkg" / "sub" / "b.py").write_text("b = 2\n")
        (root / "pkg" / "__pycache__" / "c.py").write_text("c = 3\n")
        (root / "lone.py").write_text("d = 4\n")
        got = mod.collect_sources([root / "pkg", root / "lone.py"])
        names = {p.rsplit("/", 1)[-1] for p in got}
        assert names == {"a.py", "b.py", "lone.py"}
        assert got[(root / "pkg" / "a.py").as_posix()] == "a = 1\n"


def lint_project_oracle(mod: types.ModuleType) -> None:
    """Behavioral spec of tools/lint/project.py: every registry the
    cross-file rules query, extracted from small in-memory trees with
    exact expected contents. A surviving mutant is a ProjectGraph that
    silently drops (or invents) a registry entry — a whole-program rule
    gone blind while the gate stays green."""
    import tempfile
    from pathlib import Path as _Path

    from mcp_context_forge_tpu.tools.lint.core import FileContext

    def build(sources, docs_text=None):
        ctxs = [FileContext.from_source(src, path)
                for path, src in sorted(sources.items())]
        return mod.ProjectGraph.build(ctxs, docs_text=docs_text)

    # ---- site dataclasses are frozen value objects (rules dedupe them
    # in sets — an unfrozen mutant is unhashable)
    assert len({mod.Site("a.py", 1), mod.Site("a.py", 1)}) == 1
    assert len({mod.RpcSite("a.py", 1, "unary"),
                mod.RpcSite("a.py", 1, "unary")}) == 1
    assert len({mod.MetricDecl("a", "n", (), "p", 1)}) == 1
    assert len({mod.LockDecl("k", "", "threading", "p", 1)}) == 1

    # ---- Bus-RPC registry: register/register_stream (positional and
    # keyword names), call/call_stream with timeout detection, literal
    # names resolved through same-class forwarders (keyword AND
    # positional passing); dotless names and non-rpc receivers never
    # count, on the direct path or the forwarder path
    rpc_server = (
        'class Srv:\n'
        '    def __init__(self, rpc):\n'
        '        rpc.register("pool.status", self._st)\n'
        '        rpc.register_stream("pool.tail", self._tl)\n'
        '        rpc.register(method="pool.kw", handler=self._kw)\n'
        '        rpc.register("nodot", self._nd)\n'
        '        other.register("pool.ghost", self._gh)\n'
    )
    rpc_client = (
        'class Cli:\n'
        '    def __init__(self, rpc):\n'
        '        self._rpc = rpc\n'
        '    def plain(self, w):\n'
        '        return self._rpc.call(w, "pool.status")\n'
        '    def timed(self, w):\n'
        '        return self._rpc.call(w, "pool.status", timeout_s=1.0)\n'
        '    def tail(self, w):\n'
        '        return self._rpc.call_stream(w, "pool.tail",\n'
        '                                     idle_timeout_s=2.0)\n'
        '    def tail_bare(self, w):\n'
        '        return self._rpc.call_stream(w, "pool.tail")\n'
        '    def _fwd(self, w, method):\n'
        '        return self._rpc.call(w, method=method)\n'
        '    def via(self, w):\n'
        '        return self._fwd(w, "pool.fwd")\n'
        '    def _fwd2(self, w, m):\n'
        '        return self._rpc.call(w, m)\n'
        '    def via2(self, w):\n'
        '        return self._fwd2(w, "pool.fwd2")\n'
        '    def via_dotless(self, w):\n'
        '        return self._fwd(w, "nodotfwd")\n'
        '    def bogus(self, w):\n'
        '        return other.call(w, "pool.bogus")\n'
        '    def _notrpc(self, w, method):\n'
        '        return self.conn.call(w, method)\n'
        '    def use_notrpc(self, w):\n'
        '        return self._notrpc(w, "pool.fake")\n'
    )
    g = build({"fx/server.py": rpc_server, "fx/client.py": rpc_client})
    assert g.paths == ["fx/client.py", "fx/server.py"]
    assert set(g.rpc_registered) == {"pool.status", "pool.tail", "pool.kw"}
    st, = g.rpc_registered["pool.status"]
    assert (st.path, st.lineno, st.kind) == ("fx/server.py", 3, "unary")
    assert st.has_idle_timeout is False        # the dataclass default
    tl, = g.rpc_registered["pool.tail"]
    assert (tl.path, tl.lineno, tl.kind) == ("fx/server.py", 4, "stream")
    kw, = g.rpc_registered["pool.kw"]
    assert (kw.lineno, kw.kind) == (5, "unary")
    assert set(g.rpc_called) == {"pool.status", "pool.tail",
                                 "pool.fwd", "pool.fwd2"}
    assert sorted((c.lineno, c.kind, c.has_idle_timeout)
                  for c in g.rpc_called["pool.status"]) == [
        (5, "unary", False), (7, "unary", True)]
    assert sorted((c.lineno, c.kind, c.has_idle_timeout)
                  for c in g.rpc_called["pool.tail"]) == [
        (9, "stream", True), (12, "stream", False)]
    fwd, = g.rpc_called["pool.fwd"]
    assert (fwd.path, fwd.lineno, fwd.kind) == ("fx/client.py", 16, "unary")
    fwd2, = g.rpc_called["pool.fwd2"]
    assert (fwd2.lineno, fwd2.kind, fwd2.has_idle_timeout) == \
        (20, "unary", False)
    # subset-run degradation: registries anchored on an absent module
    # come out empty, never invented
    g = build({"fx/client.py": rpc_client})
    assert g.rpc_registered == {}
    assert set(g.rpc_called) == {"pool.status", "pool.tail",
                                 "pool.fwd", "pool.fwd2"}

    # ---- SignalBus names: sync publishes on signal-shaped receivers
    # only (awaited / dict-payload calls are the EventBus twin), valid
    # dotted lowercase names only, f-strings as dynamic prefixes; reads
    # via get/ewma/replicas including the forwarder and const-tuple-loop
    # idioms
    signal_engine = (
        'class Eng:\n'
        '    def step(self, signals, shard):\n'
        '        signals.publish("llm.occupancy", 0.5)\n'
        '        signals.publish(f"slo.burn.{shard}", 1.0)\n'
        '        signals.publish(f"nodot{shard}", 1.0)\n'
        '        signals.publish("UPPER.Name", 1.0)\n'
        '        signals.publish("flat", 1.0)\n'
        '        signals.publish("llm.unread", 1.0)\n'
        '    async def emit(self, bus):\n'
        '        await bus.publish("llm.event", {"k": 1})\n'
        '        await bus.publish("llm.awaited", 1.0)\n'
        '    def dictpub(self, bus):\n'
        '        bus.publish("llm.dictpay", {"k": 1})\n'
        '    def other(self, queue):\n'
        '        queue.publish("llm.queue", 1.0)\n'
        '    def qread(self, queue, rid):\n'
        '        queue.get("llm.qread", rid)\n'
        '    def badargs(self, signals, shard):\n'
        '        signals.publish(5, 1.0)\n'
        '        signals.publish(f"{shard}.dyn", 1.0)\n'
    )
    signal_ctl = (
        '_MOD_SIGS = ("ctl.mod_sig",)\n'
        '\n'
        'class Ctl:\n'
        '    _EFFECTS = ("llm.eff_a", "llm.eff_b")\n'
        '    _LIMIT = 3\n'
        '    def __init__(self, bus):\n'
        '        self.bus = bus\n'
        '    def _view(self, name, rid):\n'
        '        return self.bus.get(name, rid)\n'
        '    def tick(self, rid):\n'
        '        a = self.bus.get("llm.occupancy", rid)\n'
        '        b = self.bus.ewma("llm.ew", rid)\n'
        '        c = self.bus.replicas("llm.rep", rid)\n'
        '        d = self._view("llm.via_fwd", rid)\n'
        '        for name in self._EFFECTS:\n'
        '            self.bus.get(name, rid)\n'
        '        return a, b, c, d\n'
        '    def probe(self, rid):\n'
        '        for name in self._LIMIT:\n'
        '            self.bus.get(name, rid)\n'
        '    def modloop(self, rid):\n'
        '        for name in _MOD_SIGS:\n'
        '            self.bus.get(name, rid)\n'
        '    def bad_fwd(self, rid):\n'
        '        return self._view("NotValid.Name", rid)\n'
        '    def _notsig(self, name, rid):\n'
        '        return self.store.get(name, rid)\n'
        '    def use_notsig(self, rid):\n'
        '        return self._notsig("fake.sig", rid)\n'
    )
    signal_pump = (
        '_SIGS = ("mod.one", "mod.two")\n'
        '_MIXED = ("bad.mix", 3)\n'
        '\n'
        'def pump(my_signals, rid):\n'
        '    for s in _SIGS:\n'
        '        my_signals.get(s, rid)\n'
    )
    g = build({"fx/eng.py": signal_engine, "fx/ctl.py": signal_ctl,
               "fx/pump.py": signal_pump})
    assert set(g.signal_published) == {"llm.occupancy", "llm.unread"}
    pub, = g.signal_published["llm.occupancy"]
    assert (pub.path, pub.lineno) == ("fx/eng.py", 3)
    assert [(p, s.lineno) for p, s in g.signal_prefixes] == \
        [("slo.burn.", 4)]
    assert set(g.signal_read) == {
        "llm.occupancy", "llm.ew", "llm.rep", "llm.via_fwd",
        "llm.eff_a", "llm.eff_b", "ctl.mod_sig", "mod.one", "mod.two"}
    assert g.signal_read["llm.via_fwd"][0].lineno == 14
    assert {s.lineno for s in g.signal_read["llm.eff_a"]} == {16}
    assert g.signal_read["ctl.mod_sig"][0].lineno == 23
    assert g.signal_read["mod.one"][0] == mod.Site("fx/pump.py", 6)
    # only all-string tuples are consts (the mixed one must not index)
    assert g.module_consts["fx/pump.py"] == {"_SIGS": ("mod.one",
                                                       "mod.two")}

    # ---- FaultPlane: the FAULT_POINTS literal counts only in a file
    # named faults.py; fault_point("name") sites count bare or dotted
    faults_mod = 'FAULT_POINTS = ("db.write", "rpc.send")\n'
    fault_user = (
        'def crash(plane):\n'
        '    fault_point("db.write")\n'
        '    plane.fault_point("rpc.send")\n'
    )
    g = build({"fx/observability/faults.py": faults_mod,
               "fx/db.py": fault_user})
    assert set(g.fault_points) == {"db.write", "rpc.send"}
    assert g.fault_points["db.write"] == mod.Site(
        "fx/observability/faults.py", 1)
    assert {n: [s.lineno for s in sites]
            for n, sites in g.fault_calls.items()} == {
        "db.write": [2], "rpc.send": [3]}
    g = build({"fx/other.py": faults_mod})
    assert g.fault_points == {}
    assert g.module_consts["fx/other.py"]["FAULT_POINTS"] == \
        ("db.write", "rpc.send")

    # ---- Prometheus metrics: declared only inside *Registry* classes;
    # labels from the positional list or the labelnames keyword
    metrics_src = (
        'class MeterRegistry:\n'
        '    def __init__(self):\n'
        '        self.tpot = Histogram("llm_tpot_s", "h",\n'
        '                              ["tenant", "phase"])\n'
        '        self.codes = Counter("http_total", "h",\n'
        '                             labelnames=("code",))\n'
        '        self.plain = Gauge("up", "h")\n'
        '        self.notmetric = dict()\n'
        '        self.version = "1.0"\n'
        '        self.weird = Counter(NAME_CONST, "h")\n'
        '        self.num = Gauge(7, "h")\n'
        '        self.empty = Counter()\n'
        '\n'
        'class Helper:\n'
        '    def __init__(self):\n'
        '        self.stray = Counter("stray_total", "h")\n'
    )
    g = build({"fx/metrics.py": metrics_src})
    assert set(g.metrics) == {"tpot", "codes", "plain"}
    assert g.metrics["tpot"].labels == ("tenant", "phase")
    assert g.metrics["tpot"].name == "llm_tpot_s"
    assert g.metrics["tpot"].lineno == 3
    assert g.metrics["codes"].labels == ("code",)
    assert g.metrics["plain"].labels == ()

    # ---- Config knobs: Settings fields only in config.py (private and
    # model_config skipped), EngineConfig fields anywhere; attr_reads
    # indexes plain attributes AND getattr/hasattr string literals
    config_src = (
        'class Settings:\n'
        '    alpha: int = 1\n'
        '    ghost_knob: int = 2\n'
        '    _hidden: int = 3\n'
        '    model_config: dict = {}\n'
        '\n'
        'class EngineConfig:\n'
        '    pages: int = 8\n'
    )
    reader_src = (
        'def use(cfg):\n'
        '    if hasattr(cfg, "maybe_knob"):\n'
        '        return cfg.alpha + getattr(cfg, "opt_knob", 0)\n'
        '    return 0\n'
    )
    g = build({"fx/config.py": config_src, "fx/reader.py": reader_src})
    assert set(g.settings_fields) == {"alpha", "ghost_knob"}
    assert g.settings_fields["alpha"] == mod.Site("fx/config.py", 2)
    assert set(g.engine_fields) == {"pages"}
    assert g.attr_reads.get("alpha") == {"fx/reader.py"}
    assert g.attr_reads.get("maybe_knob") == {"fx/reader.py"}
    assert g.attr_reads.get("opt_knob") == {"fx/reader.py"}
    assert "ghost_knob" not in g.attr_reads
    g = build({"fx/not_config.py": config_src})
    assert g.settings_fields == {} and set(g.engine_fields) == {"pages"}

    # ---- Locks, classes, call structure
    locks_src = (
        'import threading\n'
        'import asyncio\n'
        'from os import path\n'
        '\n'
        '_IO_LOCK = threading.Lock()  # lint: lock[io]\n'
        '\n'
        'class Pool:\n'
        '    def __init__(self, clamp=None):\n'
        '        self._sched_lock = threading.Lock()'
        '  # lint: lock[sched]\n'
        '        self._stats_lock = threading.RLock()\n'
        '        self._gate = asyncio.Lock()\n'
        '        self._clamp = clamp or TenantClamp()\n'
        '    def grab(self):\n'
        '        with self._sched_lock:\n'
        '            self._note()\n'
        '    def _note(self):\n'
        '        pass\n'
    )
    g = build({"fx/pool.py": locks_src})
    assert set(g.locks) == {"pool.py:_IO_LOCK", "Pool._sched_lock",
                            "Pool._stats_lock", "Pool._gate"}
    io_lock = g.locks["pool.py:_IO_LOCK"]
    assert (io_lock.context, io_lock.kind, io_lock.lineno) == \
        ("io", "threading", 5)
    sched = g.locks["Pool._sched_lock"]
    assert (sched.context, sched.kind, sched.lineno) == \
        ("sched", "threading", 9)
    assert g.locks["Pool._stats_lock"].kind == "rlock"
    assert g.locks["Pool._gate"].kind == "asyncio"
    info = g.classes[("fx/pool.py", "Pool")]
    assert set(info.methods) == {"__init__", "grab", "_note"}
    assert info.attr_types == {"_clamp": "TenantClamp"}
    assert g.class_of_attr("fx/pool.py", "Pool", "_clamp") == "TenantClamp"
    assert g.class_of_attr("fx/pool.py", "Pool", "_gate") is None
    assert g.self_calls[("fx/pool.py", "Pool", "grab")] == {"_note"}
    assert g.functions[("fx/pool.py", "Pool.grab")] == 13
    assert g.imports["fx/pool.py"] == {"threading", "asyncio", "os"}

    # ---- find_class: simple name resolves only when unambiguous
    dup = 'class Dup:\n    pass\n'
    uniq = 'class Uniq:\n    pass\n'
    g = build({"fx/a.py": dup + uniq, "fx/b.py": dup})
    assert g.find_class("Uniq").path == "fx/a.py"
    assert g.find_class("Dup") is None
    assert g.find_class("Missing") is None
    assert sorted(g.class_index["Dup"]) == [("fx/a.py", "Dup"),
                                            ("fx/b.py", "Dup")]

    # ---- docs: in-memory fixture paths (not on disk) discover None;
    # an explicit docs_text (even empty) passes through verbatim; a
    # real tree finds the docs/ sibling, all *.md files sorted
    assert build({"fx/a.py": "x = 1\n"}).docs_text is None
    assert build({"fx/a.py": "x = 1\n"},
                 docs_text="alpha knob").docs_text == "alpha knob"
    assert build({"fx/a.py": "x = 1\n"}, docs_text="").docs_text == ""
    with tempfile.TemporaryDirectory() as tmp:
        root = _Path(tmp)
        (root / "proj" / "pkg").mkdir(parents=True)
        # a docs/ dir with no .md files does not count — the walk keeps
        # climbing to the real one
        (root / "proj" / "pkg" / "docs").mkdir()
        (root / "proj" / "docs").mkdir()
        (root / "proj" / "docs" / "a.md").write_text("ALPHA")
        (root / "proj" / "docs" / "b.md").write_text("BETA")
        mod_path = root / "proj" / "pkg" / "mod.py"
        mod_path.write_text("x = 1\n")
        ctx = FileContext.from_source("x = 1\n", mod_path.as_posix())
        assert mod.ProjectGraph.build([ctx]).docs_text == "ALPHA\nBETA"

    # ---- dump(): the debug snapshot carries every registry
    g = build({"fx/server.py": rpc_server, "fx/eng.py": signal_engine,
               "fx/metrics.py": metrics_src})
    d = g.dump()
    assert d["rpc_registered"] == ["pool.kw", "pool.status", "pool.tail"]
    assert d["signal_published"] == ["llm.occupancy", "llm.unread"]
    assert d["signal_prefixes"] == ["slo.burn."]
    assert d["metrics"] == {"tpot": ["tenant", "phase"],
                            "codes": ["code"], "plain": []}


TARGETS: dict[str, MutationTarget] = {
    "jsonrpc": MutationTarget(
        rel_path="jsonrpc.py",
        module_name="mcp_context_forge_tpu.jsonrpc",
        package="mcp_context_forge_tpu",
        oracle=jsonrpc_oracle,
    ),
    "role_resolver": MutationTarget(
        rel_path="services/role_service.py",
        module_name="mcp_context_forge_tpu.services.role_service",
        package="mcp_context_forge_tpu.services",
        oracle=role_resolver_oracle,
        class_name="RoleGrantResolver",
    ),
    "auth_context": MutationTarget(
        rel_path="services/auth_service.py",
        module_name="mcp_context_forge_tpu.services.auth_service",
        package="mcp_context_forge_tpu.services",
        oracle=auth_context_oracle,
        class_name="AuthContext",
    ),
    "quantize": MutationTarget(
        rel_path="tpu_local/quantize.py",
        module_name="mcp_context_forge_tpu.tpu_local.quantize",
        package="mcp_context_forge_tpu.tpu_local",
        oracle=lambda mod: (quantize_oracle(mod),
                            _quantize_moe_and_scale_spec(mod)),
    ),
    "page_allocator": MutationTarget(
        rel_path="tpu_local/kv/paged_cache.py",
        module_name="mcp_context_forge_tpu.tpu_local.kv.paged_cache",
        package="mcp_context_forge_tpu.tpu_local.kv",
        oracle=lambda mod: (page_allocator_oracle(mod),
                            _avg_slot_pages_spec(mod),
                            _dirty_tracking_spec(mod),
                            _state_row_spec(mod),
                            _pregrant_block_spec(mod),
                            _prefix_tier_spec(mod)),
        class_name="PageAllocator",
        # _take_page's `key is not None and _cached.get(key) == page` —
        # register_prefix maintains _page_key[page] == key iff
        # _cached[key] == page, so the second conjunct is purely
        # defensive and And->Or is equivalent under the invariant; and
        # the defensive ref-default in _release_page (allocate/extend/
        # match always set a ref first, so the default is unreachable).
        equivalent_markers=(
            "key is not None and self._cached.get(key) == page",
            "current = self._ref.get(page, 1)"),
    ),
    "fabric_index": MutationTarget(
        rel_path="tpu_local/kv/fabric/index.py",
        module_name="mcp_context_forge_tpu.tpu_local.kv.fabric.index",
        package="mcp_context_forge_tpu.tpu_local.kv.fabric",
        oracle=_fabric_index_spec,
        # the advert size cap is an arbitrary tunable (the spec reads
        # mod.MAX_ADVERT_HASHES, so truncation behavior is pinned at
        # whatever the cap is; nudging the constant by one is
        # behaviorally equivalent)
        equivalent_markers=("MAX_ADVERT_HASHES = 4096",),
    ),
    "eventstream": MutationTarget(
        rel_path="utils/eventstream.py",
        module_name="mcp_context_forge_tpu.utils.eventstream",
        package="mcp_context_forge_tpu.utils",
        oracle=eventstream_oracle,
        # Contract-equivalent mutants (the oracle's contract is "raises
        # EventStreamError"; which check fires is unobservable): the
        # decode_frame short-frame guard (downstream CRC/length checks
        # also raise); prelude-offset shifts (observable only in frames
        # with a >16 MB segment — leading length bytes are 0 below
        # 2^24); the iter_frames fail-fast guard (its removal/loosening
        # still ends in decode_frame or trailing-bytes raising; the
        # 16 MB cap value itself is an arbitrary tunable).
        equivalent_markers=(
            "if len(frame) < _PRELUDE_LEN + _CRC_LEN",
            'raise EventStreamError("frame shorter than prelude")',
            "total = int.from_bytes(frame[0:4]",
            "headers_len = int.from_bytes(frame[4:8]",
            "total = int.from_bytes(buf[0:4]",
            "if total < _PRELUDE_LEN + _CRC_LEN or total > 16",
            'raise EventStreamError(f"implausible frame length',
            "if len(buf) < total",
            # the buffering loop condition `len(buf) >= _PRELUDE_LEN` vs
            # `>`: at exactly prelude-many bytes the loop just waits for
            # the next chunk — frame decoding is unchanged
            "while len(buf) >= _PRELUDE_LEN"),
    ),
    "tool_calls": MutationTarget(
        rel_path="tpu_local/tool_calls.py",
        module_name="mcp_context_forge_tpu.tpu_local.tool_calls",
        package="mcp_context_forge_tpu.tpu_local",
        oracle=tool_calls_oracle,
        # `0 <= start < end` Lt->LtE — find(open) and rfind(close) are
        # different characters, so start == end is unsatisfiable.
        equivalent_markers=("if 0 <= start < end:",),
    ),
    "lint_core": MutationTarget(
        rel_path="tools/lint/core.py",
        module_name="mcp_context_forge_tpu.tools.lint.core",
        package="mcp_context_forge_tpu.tools.lint",
        oracle=lint_core_oracle,
        # `exc.lineno or 0`: the fallback fires only when a SyntaxError
        # carries no line number, which CPython's parser never produces
        # for the sources a lint run feeds it — nudging the constant is
        # unobservable
        equivalent_markers=("exc.lineno or 0",),
    ),
    "lint_project": MutationTarget(
        rel_path="tools/lint/project.py",
        module_name="mcp_context_forge_tpu.tools.lint.project",
        package="mcp_context_forge_tpu.tools.lint",
        oracle=lint_project_oracle,
        # basename via rsplit("/", 1)[-1]: nudging maxsplit only adds
        # splits LEFT of the one [-1] reads — the basename is identical
        equivalent_markers=('ctx.path.rsplit("/", 1)[-1]',),
    ),
    "rate_limiter": MutationTarget(
        rel_path="gateway/middleware.py",
        module_name="mcp_context_forge_tpu.gateway.middleware",
        package="mcp_context_forge_tpu.gateway",
        oracle=rate_limiter_oracle,
        class_name="RateLimiter",
        # the max_buckets DEFAULT — nudging the 100_000 cap by one is
        # behaviorally equivalent (oracle passes explicit caps); and the
        # sweep-trigger compare `now >= _next_sweep` vs `>` differs only
        # at exact monotonic-clock equality (measure zero — the sweep
        # fires one tick later)
        equivalent_markers=("max_buckets: int = 100_000",
                            "now >= self._next_sweep"),
    ),
}
