"""Server-rendered admin UI.

Reference: 20.5k-LoC admin.py + 34.8k-LoC JS admin_ui — intentionally
table-driven here (SURVEY.md §7.2 #5: the API surface must be generated,
not hand-grown). One page, vanilla JS over the existing REST API:

- entity tabs with client-side search + auto-refresh + cursor paging
- full CRUD where the API has it: create forms (per-entity field specs,
  typed fields ``name:int`` / ``name:csv`` / ``name:json``), JSON edit
  (PUT), delete, enable/disable toggles
- per-entity DETAIL views (key-value pane + related records: team
  members with add/remove/invite, token mint-once reveal, plugin mode
  dropdowns posting /plugins/{name}/mode)
- metrics dashboard: totals cards + hourly rollup bar chart (pure divs)
- export/import pane: download the config bundle, paste-to-import with
  overwrite toggle
- trace drill-down: span tree AND a gantt view; engine stat cards

The UI contract test (`tests/integration/test_admin_ui_contract.py` +
`test_admin_ui_coverage.py`) asserts every admin REST endpoint is
reachable from this page — the JS-free browser tier (no node/playwright
in the image; the reference uses `tests/playwright/`).
"""

from __future__ import annotations

from aiohttp import web

_PAGE = """<!doctype html>
<html><head><meta charset="utf-8"><title>mcpforge admin</title>
<style>
 body{font-family:system-ui,sans-serif;margin:0;background:#f4f5f7;color:#1a1d21}
 header{background:#1a1d21;color:#fff;padding:10px 20px;display:flex;gap:16px;align-items:center;flex-wrap:wrap}
 header h1{font-size:16px;margin:0}
 nav button{background:none;border:none;color:#aab;cursor:pointer;font-size:14px;padding:6px 10px}
 nav button.active{color:#fff;border-bottom:2px solid #6cf}
 main{padding:20px;max-width:1200px;margin:0 auto}
 table{width:100%;border-collapse:collapse;background:#fff;box-shadow:0 1px 3px rgba(0,0,0,.08)}
 th,td{text-align:left;padding:8px 12px;border-bottom:1px solid #eceef1;font-size:13px}
 th{background:#fafbfc;font-weight:600}
 .pill{display:inline-block;padding:1px 8px;border-radius:10px;font-size:11px}
 .ok{background:#d9f2e4;color:#11734b}.bad{background:#fde2e1;color:#a12622}
 #bar{margin:10px 0;display:flex;gap:10px;align-items:center;flex-wrap:wrap}
 #status{color:#667}
 #q{padding:6px 10px;border:1px solid #ccd;border-radius:4px;min-width:220px}
 button.act{background:#eef;border:1px solid #ccd;border-radius:4px;cursor:pointer;padding:2px 8px;font-size:12px}
 button.danger{background:#fde2e1;border-color:#eab}
 a.trace{color:#26c;cursor:pointer;text-decoration:underline}
 #detail{background:#fff;margin-top:14px;padding:12px;box-shadow:0 1px 3px rgba(0,0,0,.08);display:none}
 .span-row{font-family:ui-monospace,monospace;font-size:12px;white-space:pre}
 .err{color:#a12622}
 #form{background:#fff;margin:10px 0;padding:12px;box-shadow:0 1px 3px rgba(0,0,0,.08);display:none}
 #form input,#detail input,#detail select{margin:3px 6px 3px 0;padding:5px 8px;border:1px solid #ccd;border-radius:4px}
 #edit-area,#import-area{width:100%;min-height:140px;font-family:ui-monospace,monospace;font-size:12px}
 .gantt{position:relative;height:18px;margin:1px 0;background:#fafbfc}
 .gantt .bar{position:absolute;top:2px;height:14px;background:#9cf;border-radius:2px;min-width:2px}
 .gantt .bar.err{background:#f99}
 .gantt .lbl{position:absolute;left:4px;top:1px;font-size:11px;font-family:ui-monospace,monospace;white-space:nowrap;z-index:1}
 .cards{display:flex;gap:12px;flex-wrap:wrap}
 .card{background:#fff;box-shadow:0 1px 3px rgba(0,0,0,.08);padding:12px 18px;min-width:130px}
 .card b{display:block;font-size:22px}.card span{color:#667;font-size:12px}
 .kv{font-family:ui-monospace,monospace;font-size:12px}
 .kv td{padding:3px 10px}
 .chart{display:flex;align-items:flex-end;gap:2px;height:120px;background:#fff;padding:10px;box-shadow:0 1px 3px rgba(0,0,0,.08);margin-top:10px}
 .chart .col{flex:1;display:flex;flex-direction:column;justify-content:flex-end;height:100%}
 .chart .v{background:#9cf;min-height:1px}
 .chart .e{background:#f99}
 .chart .t{font-size:9px;color:#889;text-align:center;overflow:hidden}
 select.mode{font-size:12px;padding:2px}
 .reveal{background:#fffbe6;border:1px solid #eda;padding:8px;margin:8px 0;font-family:ui-monospace,monospace;font-size:12px;word-break:break-all}
</style></head><body>
<header><h1>mcpforge</h1><nav id="nav"></nav></header>
<main>
 <div id="bar">
  <input id="q" placeholder="filter rows…" oninput="render()">
  <button class="act" onclick="show(current)">refresh</button>
  <button class="act" id="newbtn" onclick="openForm()" style="display:none">+ new</button>
  <button class="act" id="morebtn" onclick="nextPage()" style="display:none">next page ▸</button>
  <label style="font-size:12px;color:#667"><input type="checkbox" id="auto"
   onchange="autoRefresh()"> auto (5s)</label>
  <span id="status"></span>
 </div>
 <div id="form"></div>
 <div id="view"></div>
 <div id="detail"></div>
</main>
<script src="/admin/app.js"></script>
</body></html>"""

# The page's JavaScript, served as its own asset (/admin/app.js) so
# it is a TESTABLE MODULE: tests/integration/test_admin_js_render.py
# extracts and EXECUTES its pure render functions (no JS runtime in
# the CI image; a mechanical subset translator runs them in-process).
_JS = r"""// double-submit CSRF: echo the csrf_token cookie on every fetch — a
// cross-site page can make the browser SEND the cookie but cannot READ
// it, so the echo proves this same-origin script issued the request
const _fetch = window.fetch.bind(window);
window.fetch = (url, opts) => {
  opts = opts || {};
  const m = document.cookie.match(/(?:^|; )csrf_token=([^;]*)/);
  opts.headers = Object.assign({}, opts.headers,
                               m ? {"X-CSRF-Token": m[1]} : {});
  return _fetch(url, opts);
};
const TABS = {
  tools:    {paged:true, url: "/tools?include_inactive=true", cols: ["name","integration_type","url","enabled","reachable"], toggle: id => `/tools/${id}/toggle`, boolcols: ["enabled","reachable"],
             create: {url:"/tools", fields:["name","integration_type","url","description","tags:csv"]},
             edit: id => `/tools/${id}`, del: id => `/tools/${id}`,
             detail: id => `/tools/${id}`,
             rowacts: [{label:"gen cases", method:"GET", key:"name", show:true, url: n => `/toolops/${encodeURIComponent(n)}/cases`},
                       {label:"run cases", method:"POST", key:"name", show:true, url: n => `/toolops/${encodeURIComponent(n)}/run`}]},
  gateways: {paged:true, url: "/gateways?include_inactive=true", cols: ["name","url","transport","state","reachable"], boolcols: ["reachable"],
             create: {url:"/gateways", fields:["name","url","transport"],
                      testurl: "/gateways/test"},
             edit: id => `/gateways/${id}`, del: id => `/gateways/${id}`,
             detail: id => `/gateways/${id}`,
             rowacts: [{label:"resync", method:"POST", url: id => `/gateways/${id}/refresh`}]},
  servers:  {paged:true, url: "/servers?include_inactive=true", cols: ["name","description","associated_tools","enabled"], boolcols: ["enabled"],
             create: {url:"/servers", fields:["name","description","associated_tools:csv"]},
             edit: id => `/servers/${id}`, del: id => `/servers/${id}`,
             detail: id => `/servers/${id}`},
  resources:{paged:true, url: "/resources?include_inactive=true", cols: ["uri","name","mime_type","enabled"], boolcols: ["enabled"],
             create: {url:"/resources", fields:["uri","name","content","mime_type"]},
             edit: id => `/resources/${id}`, del: id => `/resources/${id}`},
  prompts:  {paged:true, url: "/prompts?include_inactive=true", cols: ["name","description","enabled"], boolcols: ["enabled"],
             create: {url:"/prompts", fields:["name","template","description"]},
             edit: id => `/prompts/${id}`, del: id => `/prompts/${id}`},
  agents:   {paged:true, url: "/a2a?include_inactive=true", cols: ["name","agent_type","endpoint_url","enabled","reachable"], boolcols: ["enabled","reachable"],
             create: {url:"/a2a", fields:["name","agent_type","endpoint_url"]},
             del: id => `/a2a/${id}`},
  plugins:  {url: "/plugins", cols: ["name","kind","mode","priority"], special: "plugins"},
  bindings: {url: "/plugins/bindings", cols: ["plugin_name","scope_type","scope_id","mode","enabled"], boolcols: ["enabled"],
             create: {url:"/plugins/bindings", fields:["plugin_name","scope_type","scope_id","mode","config:json"]},
             del: id => `/plugins/bindings/${id}`},
  users:    {paged:true, url: "/admin/users", cols: ["email","full_name","is_admin","is_active","auth_provider","last_login"], toggle: id => `/admin/users/${encodeURIComponent(id)}/toggle`, idcol: "email", boolcols: ["is_admin","is_active"],
             create: {url:"/admin/users", fields:["email","password","full_name"]},
             rowacts: [{label:"require pw change", method:"POST", key:"email", show:true, url: e => `/admin/users/${encodeURIComponent(e)}/require-password-change`}]},
  teams:    {url: "/teams", cols: ["name","slug","visibility","is_personal","created_by"], boolcols: ["is_personal"],
             create: {url:"/teams", fields:["name","visibility"]},
             del: id => `/teams/${id}`, detail: id => `/teams/${id}`, special: "teams"},
  config:   {url: "/admin/config", cols: ["name","value"]},
  compliance: {url: "/compliance/reports", cols: ["framework","generated_at","generated_by","summary"],
             create: {url:"/compliance/reports", fields:["framework","period_days:int"]},
             detail: id => `/compliance/reports/${id}`,
             rowacts: [{label:"export md", method:"GET", show:true, url: id => `/compliance/reports/${id}/export?format=markdown`},
                       {label:"frameworks", method:"GET", show:true, url: () => `/compliance/frameworks`}]},
  roles:    {paged:true, url: "/rbac/roles", cols: ["name","scope","description","is_system","assignment_count"], boolcols: ["is_system"],
             create: {url:"/rbac/roles", fields:["name","description","scope","permissions:csv"]},
             del: id => `/rbac/roles/${id}`, detail: id => `/rbac/roles/${id}`, special: "roles"},
  tokens:   {url: "/auth/tokens", cols: ["name","server_id","expires_at","last_used","revoked_at"],
             create: {url:"/auth/tokens", fields:["name","expires_minutes:int","permissions:csv","server_id"], reveal: "token"},
             del: id => `/auth/tokens/${id}`,
             rowacts: [{label:"usage", method:"GET", show:true, url: id => `/auth/tokens/${id}/usage`}]},
  providers:{url: "/llm/providers", cols: ["name","provider_type","api_base","enabled"], boolcols: ["enabled"],
             create: {url:"/llm/providers", fields:["name","provider_type","api_base","api_key"]},
             del: id => `/llm/providers/${id}`},
  models:   {url: "/v1/models", cols: ["id","owned_by"], path: "data"},
  llmmodels:{url: "/llm/models", cols: ["model_alias","provider_id","enabled"], boolcols: ["enabled"]},
  ingress:  {url: "/admin/ingress", special: "ingress"},
  dashboard:{special: "dashboard"},
  metrics:  {url: "/metrics", cols: ["name","calls","errors","avg_ms","min_ms","max_ms"], path: "tools"},
  rollups:  {url: "/metrics/rollups", cols: ["entity_type","entity_id","hour","calls","errors","avg_ms"]},
  traces:   {url: "/admin/traces?limit=100", cols: ["name","duration_ms","status","trace_id"], tracecol: "trace_id"},
  logs:     {url: "/admin/logs?limit=200", cols: ["ts","level","logger","message"]},
  audit:    {url: "/admin/audit?limit=100", cols: ["ts","actor","action","details"]},
  exportimport: {special: "exportimport"},
  chat:     {special: "chat"},
  engine:   {url: "/admin/engine/stats", special: "engine"},
  gateway:  {url: "/admin/gateway/requests?limit=24", special: "gwflight"},
  forensics:{url: "/admin/trace?limit=50", special: "forensics"},
  controller:{url: "/admin/controller?limit=32", special: "controller"},
  tenants:  {url: "/admin/tenants/usage?limit=32", special: "tenants"},
  diagnostics: {special: "diagnostics"},
};
let current = "tools", rows = [], shown = [], timer = null, cursor = null;
function esc(s){
  return String(s).replace(/[&<>"']/g, c => ({"&":"&amp;","<":"&lt;",">":"&gt;",
    '"':"&quot;","'":"&#39;"}[c]));
}
function cell(v, isBool){
  // booleanness is a per-COLUMN decision (sqlite int-bools), never by value
  if (isBool) return (v === true || v === 1)
    ? '<span class="pill ok">yes</span>' : '<span class="pill bad">no</span>';
  if (v === true) return '<span class="pill ok">yes</span>';
  if (v === false) return '<span class="pill bad">no</span>';
  if (Array.isArray(v)) return v.length;
  if (v === null || v === undefined) return "";
  if (typeof v === "number") return Math.round(v*100)/100;
  if (typeof v === "object") return esc(JSON.stringify(v).slice(0,80));
  return esc(String(v).slice(0,100));  // API data is attacker-influenced
}
function fnum(v){
  // roofline fractions live at 1e-2..1e-8 (MFU 0.00018 is the headline
  // production number) — cell()'s 2-decimal rounding would zero them
  if (v === null || v === undefined || typeof v !== "number") return cell(v);
  if (v !== 0 && Math.abs(v) < 0.01) return v.toExponential(2);
  return Math.round(v*10000)/10000;
}
async function renderEngine(stats){
  const order = ["requests","prompt_tokens","completion_tokens","decode_steps",
                 "decode_dispatches",
                 "prefill_batches","queue_depth","chunking","kv_pages_in_use",
                 "kv_bytes_in_use","kv_quant",
                 "prefix_hits","prefix_hit_tokens","tier_hits_host",
                 "tier_hits_disk","tier_hits_object",
                 "tier_hit_tokens_spilled",
                 "spec_steps","spec_tokens",
                 "overlap_steps","pipeline_drains","first_flushes",
                 "dispatch_gap_ms_total",
                 "prefill_ms_total","decode_ms_total","engine_restarts"];
  const cards = order.filter(k => k in stats).map(k =>
    `<div class="card"><b>${cell(stats[k])}</b><span>${k}</span></div>`).join("");
  const rest = Object.keys(stats).filter(k => !order.includes(k));
  const extra = rest.map(k =>
    `<div class="card"><b>${cell(stats[k])}</b><span>${k}</span></div>`).join("");
  // replica pool card (multi-replica serving tier; 404 when replicas=1)
  let pool = "";
  try {
    const pr = await fetch("/admin/engine/pool");
    if (pr.ok){
      const p = await pr.json();
      const pcols = ["id","state","role","occupancy","outstanding",
                     "outstanding_tokens","kv_pages_in_use","routed",
                     "requeued_off","migrations_out","migrations_in",
                     "reloads","failures","heartbeat_age_s"];
      const pbody = (p.replicas || []).map(rp =>
        "<tr>" + pcols.map(c => `<td>${cell(rp[c])}</td>`).join("")
        + `<td><button class="act" onclick="poolAct('${esc(rp.id)}','drain')">drain</button>
           <button class="act" onclick="poolAct('${esc(rp.id)}','undrain')">undrain</button>
           <button class="act" onclick="poolAct('${esc(rp.id)}','reload')">reload</button></td></tr>`
      ).join("");
      const mig = p.migrations || {};
      pool = `<br><h3>engine replica pool</h3>
        <div class="cards">
          <div class="card"><b>${cell((p.router||{}).routed)}</b><span>routed</span></div>
          <div class="card"><b>${cell((p.router||{}).affinity_hits)}</b><span>affinity_hits</span></div>
          <div class="card"><b>${cell((p.router||{}).role_routed)}</b><span>role_routed</span></div>
          <div class="card"><b>${cell((p.router||{}).role_spills)}</b><span>role_spills</span></div>
          <div class="card"><b>${cell(mig.ok)}</b><span>migrations_ok</span></div>
          <div class="card"><b>${cell(mig.degraded)}</b><span>migrations_degraded</span></div>
          <div class="card"><b>${cell(p.requeues)}</b><span>requeues</span></div>
          <div class="card"><b>${cell((p.health||{}).failures)}</b><span>replica_failures</span></div>
        </div>
        <table><tr>` + pcols.map(c => `<th>${esc(c)}</th>`).join("")
        + `<th>actions</th></tr>${pbody}</table>`;
    }
  } catch(e){}
  // prefix-cache fabric card (docs/cache_fabric.md; 404 when the T3
  // object tier is off — fabric stats only exist behind an object store)
  let fabric = "";
  try {
    const fr = await fetch("/admin/fabric/adverts");
    if (fr.ok){
      const f = await fr.json();
      const st = f.store || {};
      const fx = st.fabric || {};
      fabric = `<br><h3>prefix-cache fabric ${
          (st.object_breaker || {}).state === "open"
            ? '<span class="pill bad">tier.object open</span>'
            : '<span class="pill ok">serving</span>'}</h3>
        <div class="cards">
          <div class="card"><b>${cell(st.object_pages)}</b><span>object_pages</span></div>
          <div class="card"><b>${cell(st.object_bytes)}</b><span>object_bytes</span></div>
          <div class="card"><b>${cell(st.object_reads)}</b><span>object_reads</span></div>
          <div class="card"><b>${cell(st.object_writes)}</b><span>object_writes</span></div>
          <div class="card"><b>${cell(st.object_write_drops)}</b><span>object_write_drops</span></div>
          <div class="card"><b>${cell(fx.keys)}</b><span>fabric_keys</span></div>
          <div class="card"><b>${cell(fx.hosts)}</b><span>fabric_hosts</span></div>
          <div class="card"><b>${cell(fx.merged)}</b><span>adverts_merged</span></div>
          <div class="card"><b>${cell(f.sent)}</b><span>adverts_sent</span></div>
          <div class="card"><b>${cell(f.send_failures)}</b><span>advert_send_failures</span></div>
        </div>`;
    }
  } catch(e){}
  // serving SLO verdicts (percentiles + burn rate vs error budget)
  let slo = "";
  try {
    const sr = await fetch("/admin/slo?window=admin-ui");
    if (sr.ok){
      const s = await sr.json();
      const scols = ["name","target_ms","window_p_ms","cumulative_p_ms",
                     "window_samples","fraction_over_target","burn_rate","ok"];
      const sbody = (s.objectives || []).map(o =>
        "<tr>" + scols.map(c => `<td>${
          c === "fraction_over_target" || c === "burn_rate"
            ? fnum(o[c]) : cell(o[c])
        }</td>`).join("") + "</tr>"
      ).join("");
      if (sbody) slo = `<br><h3>serving SLOs ${s.ok
          ? '<span class="pill ok">within budget</span>'
          : '<span class="pill bad">burning</span>'}</h3><table><tr>`
        + scols.map(c => `<th>${esc(c)}</th>`).join("")
        + `</tr>${sbody}</table>`;
    }
  } catch(e){}
  // step introspection: what the scheduler dispatched last (newest first)
  let steps = "";
  try {
    const r = await fetch("/admin/engine/steps?limit=32");
    if (r.ok){
      const intro = await r.json();
      // compile tracking + live roofline summary cards (a serving-stage
      // XLA compile on a warmed engine is the mid-traffic catastrophe)
      const xc = intro.xla_compiles || {};
      const rf = intro.roofline || {};
      steps = `<br><h3>step attribution &amp; roofline</h3>
        <div class="cards">
          <div class="card"><b>${cell((xc.serving||{}).count)}</b><span>serving_xla_compiles</span></div>
          <div class="card"><b>${cell((xc.warmup||{}).count)}</b><span>warmup_xla_compiles</span></div>
          <div class="card"><b>${fnum(rf.mfu)}</b><span>live_mfu</span></div>
          <div class="card"><b>${fnum(rf.hbm_roofline_frac)}</b><span>live_hbm_roofline_frac</span></div>
          <div class="card"><b>${cell(intro.dispatch_stalls)}</b><span>dispatch_stalls</span></div>
        </div>`;
      const cols = ["seq","kind","batch","width","bucket","ctx_pages",
                    "duration_ms","gap_ms","tokens","superstep","frozen",
                    "mfu","hbm_frac",
                    "phases","queue_depth","kv_pages_in_use"];
      const body = (intro.steps || []).slice().reverse().map(s =>
        "<tr>" + cols.map(c => `<td>${
          c === "mfu" || c === "hbm_frac" ? fnum(s[c]) : cell(s[c])
        }</td>`).join("") + "</tr>"
      ).join("");
      if (body) steps += `<br><h3>recent engine steps</h3><table><tr>`
        + cols.map(c => `<th>${esc(c)}</th>`).join("") + `</tr>${body}</table>`;
    }
  } catch(e){}
  document.getElementById("view").innerHTML =
    `<div class="cards">${cards}${extra}</div>${pool}${fabric}${slo}${steps}
     <br><button class="act" onclick="engineProfile()">capture jax profile</button>
     <button class="act" onclick="engineProfileCtl('start')">start profile</button>
     <button class="act" onclick="engineProfileCtl('stop')">stop profile</button>
     <button class="act" onclick="engineProfileStatus()">profile status</button>`;
  document.getElementById("status").textContent = "engine stats";
}
function gwFlightTable(title, rows){
  // phase vector rendered inline: the breakdown IS the payload here
  const cols = ["ts","method","path","status","tenant","duration_ms",
                "phases_ms","error","trace_id"];
  const body = (rows || []).map(r =>
    "<tr>" + cols.map(c => {
      if (c === "phases_ms")
        return `<td class="kv">${esc(JSON.stringify(r.phases_ms || {}))}</td>`;
      if (c === "ts") return `<td>${esc(new Date((r.ts||0)*1000)
        .toISOString().slice(11,23))}</td>`;
      return `<td>${cell(r[c])}</td>`;
    }).join("") + "</tr>").join("");
  if (!body) return "";
  return `<br><h3>${esc(title)}</h3><table><tr>`
    + cols.map(c => `<th>${esc(c)}</th>`).join("") + `</tr>${body}</table>`;
}
function renderGatewayFlight(snap){
  // request flight recorder: slowest-N + recent rings with per-phase
  // breakdowns, loop-lag health, engine backpressure — the HTTP-tier
  // twin of the engine tab's step attribution card
  const loop = snap.loop || {};
  const bp = snap.backpressure || {};
  const cards = `<div class="cards">
    <div class="card"><b>${cell(snap.recorded)}</b><span>requests_recorded</span></div>
    <div class="card"><b>${cell(snap.slow_requests)}</b><span>slow_requests (&gt;${cell(snap.slow_request_ms)}ms)</span></div>
    <div class="card"><b>${cell(snap.inflight)}</b><span>in_flight</span></div>
    <div class="card"><b>${cell(loop.last_lag_ms)}</b><span>loop_lag_last_ms</span></div>
    <div class="card"><b>${cell(loop.max_lag_ms)}</b><span>loop_lag_max_ms</span></div>
    <div class="card"><b>${cell(loop.long_callbacks)}</b><span>long_callbacks</span></div>
    <div class="card"><b>${cell(bp.depth)}</b><span>engine_queue_depth</span></div>
    <div class="card"><b>${fnum(bp.saturation)}</b><span>engine_saturation</span></div>
    <div class="card"><b>${cell(snap.shed_total)}</b><span>requests_shed</span></div>
   </div>`;
  // degradation ladder (docs/resilience.md): one pill per component —
  // closed = healthy, half_open = probing recovery, open = degraded
  // path active (full breaker detail at GET /admin/faults)
  const deg = snap.degradation || {};
  const degRow = Object.keys(deg).length
    ? "<br><h3>degradation ladder</h3><div class=\"cards\">"
      + Object.keys(deg).sort().map(c =>
        `<div class="card"><b>${esc(deg[c])}</b><span>${esc(c)}</span></div>`
      ).join("") + "</div>"
    : "";
  document.getElementById("view").innerHTML = cards + degRow
    + '<br><button class="act" onclick="faultsDetail()">fault plane / breakers</button>'
    + gwFlightTable("slowest requests", snap.slowest)
    + gwFlightTable("recent requests", snap.recent);
  document.getElementById("status").textContent = "gateway flight recorder";
}
async function faultsDetail(){
  // the resilience plane (docs/resilience.md): armed fault rules with
  // fired/call counts (disarmable per point), breaker snapshots +
  // transition history, rollup outage stats, shedder counters
  const r = await fetch("/admin/faults");
  const d = document.getElementById("detail");
  d.style.display = "block";
  if (!r.ok){ d.textContent = "faults fetch failed: " + r.status; return; }
  const f = await r.json();
  faultRules = f.rules || [];
  let html = `<b>fault plane ${f.enabled ? "(ARMED)" : "(disabled)"}</b>`;
  html += faultRules.length
    ? "<table><tr><th>point</th><th>kind</th><th>mode</th><th>scope</th>"
      + "<th>fired/calls</th><th></th></tr>"
      + faultRules.map((r2, i) =>
        `<tr><td>${esc(r2.point)}</td><td>${esc(r2.kind)}</td>`
        + `<td>${esc(r2.mode)}</td><td>${esc(r2.scope||"")}</td>`
        + `<td>${cell(r2.fired)}/${cell(r2.calls)}</td>`
        + `<td><button class="act" onclick="faultDisarm(${i})">disarm</button></td></tr>`
      ).join("") + "</table>"
    : "<div class=\"kv\">no rules armed</div>";
  const deg = f.degradation || {};
  html += "<br><b>breakers</b><table><tr><th>component</th><th>key</th>"
    + "<th>state</th><th>consec</th><th>fail/ok</th></tr>"
    + (deg.breakers||[]).map(b =>
      `<tr><td>${esc(b.component)}</td><td>${esc(b.key||"")}</td>`
      + `<td>${esc(b.state)}</td><td>${cell(b.consecutive_failures)}</td>`
      + `<td>${cell(b.failures)}/${cell(b.successes)}</td></tr>`).join("")
    + "</table>";
  if (deg.rollup)
    html += `<div class="kv">rollup outage: pending ${cell(deg.rollup.pending_windows)}`
      + `/${cell(deg.rollup.pending_max)}, dropped ${cell(deg.rollup.windows_dropped)}`
      + ` window(s) / ${cell(deg.rollup.tokens_dropped)} token(s)</div>`;
  if (f.shedder)
    html += `<div class="kv">shedder: shed_total ${cell(f.shedder.shed_total)},`
      + ` bar ${fnum(f.shedder.shed_at)}, order ${esc(JSON.stringify(f.shedder.class_order))}</div>`;
  html += "<div class=\"kv\">transitions: "
    + esc((deg.transitions||[]).map(t =>
      `${t.component}:${t.from}→${t.to}`).join(", ") || "none") + "</div>";
  d.innerHTML = html;
}
let faultRules = [];
async function faultDisarm(i){
  // index-based lookup: the point name is server data and must never
  // be interpolated into an onclick JS string (tenants-tab XSS rule)
  const rule = faultRules[i];
  if (!rule) return;
  await fetch(`/admin/faults/${encodeURIComponent(String(rule.point))}`,
              {method: "DELETE"});
  faultsDetail();
}
let forensicRows = [];
function renderForensics(snap){
  // tail-sampled trace store (observability/trace_store.py): what
  // survived retention and why, each row clicking through to its
  // stitched cross-layer waterfall at /admin/trace/{id}
  forensicRows = snap.traces || [];
  const cards = `<div class="cards">
    <div class="card"><b>${cell(snap.retained)}/${cell(snap.max_traces)}</b><span>retained (budget)</span></div>
    <div class="card"><b>${cell(snap.finalized)}</b><span>traces_finalized</span></div>
    <div class="card"><b>${cell(snap.dropped)}</b><span>dropped (boring)</span></div>
    <div class="card"><b>${cell(snap.evicted)}</b><span>evicted (budget)</span></div>
    <div class="card"><b>${cell(snap.open)}</b><span>open</span></div>
    <div class="card"><b>${cell((snap.exemplars||{}).pinned_traces)}</b><span>exemplar_pins</span></div>
   </div>`;
  const cols = ["ts","root","route","tenant","status","duration_ms",
                "span_count","reasons","breaches","trace_id"];
  const body = forensicRows.map((t, i) =>
    "<tr>" + cols.map(c => {
      if (c === "ts") return `<td>${esc(new Date((t.ts||0)*1000)
        .toISOString().slice(11,23))}</td>`;
      if (c === "reasons" || c === "breaches")
        return `<td>${esc((t[c]||[]).join(","))}</td>`;
      return `<td>${cell(t[c])}</td>`;
    }).join("")
    + `<td><button class="act" onclick="forensicWaterfall(${i})">waterfall</button></td></tr>`
  ).join("");
  document.getElementById("view").innerHTML = cards
    + (body ? `<br><h3>retained traces (newest first)</h3><table><tr>`
      + cols.map(c => `<th>${esc(c)}</th>`).join("")
      + `<th></th></tr>${body}</table>`
      : "<br>no retained traces yet — drive some traffic");
  document.getElementById("status").textContent = "request forensics";
}
async function forensicWaterfall(i){
  const row = forensicRows[i];
  if (!row) return;
  const id = encodeURIComponent(String(row.trace_id || ""));
  const r = await fetch(`/admin/trace/${id}`);
  const d = document.getElementById("detail");
  d.style.display = "block";
  if (!r.ok){ d.textContent = "waterfall fetch failed: " + r.status; return; }
  const w = await r.json();
  const inv = w.invariants || {};
  const pill = ok => ok ? '<span class="pill ok">ok</span>'
                        : '<span class="pill bad">violated</span>';
  let html = `<b>waterfall ${esc(String(row.trace_id||""))}</b>
    <div class="cards">
      <div class="card"><b>${cell((w.root||{}).duration_ms)}</b><span>wall_ms (${esc((w.root||{}).name||"?")})</span></div>
      <div class="card"><b>${cell(w.span_count)}</b><span>spans</span></div>
      <div class="card"><b>${esc((w.replica_hops||[]).join(" → ")||"-")}</b><span>replica_hops</span></div>
      <div class="card"><b>${cell(w.engine_steps_joined)}</b><span>engine_steps_joined</span></div>
      <div class="card">${pill(inv.children_within_parent)}<span>children_within_parent</span></div>
      <div class="card">${pill(inv.child_cover_le_wall)}<span>child_cover_le_wall</span></div>
    </div>`;
  if (w.gateway)
    html += `<div class="kv">gateway phases (sum ${cell(w.gateway.phase_sum_ms)}ms`
      + ` / wall ${cell(w.gateway.duration_ms)}ms): `
      + `${esc(JSON.stringify(w.gateway.phases_ms||{}))}</div>`;
  // indented span rows + gantt bars over the trace window
  const flat = [];
  const walk = (node, depth) => {
    flat.push([node, depth]);
    for (const c of node.children || []) walk(c, depth+1);
  };
  for (const root of w.tree || []) walk(root, 0);
  const starts = flat.map(([s]) => s.start_ts).filter(v => v != null);
  const t0 = starts.length ? Math.min(...starts) : 0;
  const t1 = Math.max(...flat.map(([s]) =>
    (s.start_ts||t0) + ((s.duration_ms||0)/1000)), t0 + 1e-6);
  const win = t1 - t0;
  html += flat.map(([s, depth]) => {
    const left = (((s.start_ts||t0)-t0)/win)*100;
    const width = Math.max((((s.duration_ms||0)/1000)/win)*100, 0.3);
    const cls = s.status === "ERROR" ? "bar err" : "bar";
    const steps = s.engine_steps ? ` [${s.engine_steps.length} engine steps]` : "";
    return `<div class="span-row${s.status==="ERROR"?" err":""}">`
      + `${"  ".repeat(depth)}${esc(s.name)} (${esc(s.layer||"")})`
      + `  ${s.duration_ms == null ? "" : Math.round(s.duration_ms*100)/100 + "ms"}`
      + `${esc(steps)}</div>`
      + `<div class="gantt"><div class="${cls}" style="left:${left.toFixed(2)}%;width:${width.toFixed(2)}%"></div></div>`;
  }).join("");
  d.innerHTML = html;
}
function renderController(snap){
  // closed-loop serving controller (tpu_local/controller.py): the
  // decision audit ring — signal snapshot in, knob delta out, observed
  // effect after the eval window — plus live per-replica knob state
  const cards = `<div class="cards">
    <div class="card"><b>${snap.enabled ? (snap.safe_mode ? "SAFE (observe-only)" : "ACTIVE") : "off"}</b><span>controller</span></div>
    <div class="card"><b>${cell(snap.ticks)}</b><span>ticks</span></div>
    <div class="card"><b>${cell(snap.tick_s)}s / ${cell(snap.cooldown_s)}s</b><span>tick / cooldown</span></div>
    <div class="card"><b>${fnum(snap.hysteresis)}</b><span>hysteresis</span></div>
    <div class="card"><b>${fnum(snap.shed_bar)}</b><span>shed_bar (floor ${fnum(snap.shed_floor)}, ceil ${fnum(snap.shed_ceiling)})</span></div>
   </div>`;
  // per-replica knob state: what the engines are ACTUALLY running now
  const knobs = snap.knobs || {};
  const knobRows = Object.keys(knobs).sort().map(rid => {
    const k = knobs[rid] || {};
    return `<tr><td>${esc(rid)}</td><td>${cell(k.superstep)}</td>`
      + `<td>${esc(JSON.stringify(k.warmed_k||[]))}</td>`
      + `<td>${k.spec_built ? (k.spec_enabled ? "on" : "off") : "-"}</td></tr>`;
  }).join("");
  const knobTable = knobRows
    ? `<br><h3>replica knobs</h3><table><tr><th>replica</th><th>K</th>`
      + `<th>warmed_k</th><th>spec</th></tr>${knobRows}</table>`
    : "<br>no engines wired";
  // decision ring, newest first: every row says what the controller
  // saw, what it moved, and what the signals did afterwards
  const cols = ["ts","replica","knob","direction","from","to","actuated",
                "signals","effect"];
  const body = (snap.decisions || []).map(d =>
    "<tr>" + cols.map(c => {
      if (c === "ts") return `<td>${esc(new Date((d.ts||0)*1000)
        .toISOString().slice(11,23))}</td>`;
      if (c === "signals" || c === "effect")
        return `<td class="kv">${esc(JSON.stringify(d[c]||{}))}</td>`;
      if (c === "actuated") return `<td>${cell(d.actuated === true)}</td>`;
      return `<td>${cell(d[c])}</td>`;
    }).join("") + "</tr>").join("");
  const ring = body
    ? `<br><h3>decisions (newest first)</h3><table><tr>`
      + cols.map(c => `<th>${esc(c)}</th>`).join("") + `</tr>${body}</table>`
    : "<br>no decisions yet — the loop holds until signals warrant a move";
  document.getElementById("view").innerHTML = cards + knobTable + ring;
  document.getElementById("status").textContent = "serving controller";
}
async function renderTenants(usage){
  // per-tenant metering (observability/metering.py): live ledger rows,
  // quota window, label clamp, and the recent DB rollups — plus each
  // tenant's SLO-class verdict fetched per row from /admin/slo?tenant=
  const clamp = usage.clamp || {};
  const cards = `<div class="cards">
    <div class="card"><b>${cell(usage.tenant_count)}</b><span>tenants</span></div>
    <div class="card"><b>${cell(usage.rollups_written)}</b><span>rollup_rows_written</span></div>
    <div class="card"><b>${cell(usage.rollup_interval_s)}</b><span>rollup_interval_s</span></div>
    <div class="card"><b>${cell(usage.quota_tokens_per_window) || "off"}</b><span>quota_tokens_per_window</span></div>
    <div class="card"><b>${cell((clamp.admitted||[]).length)}/${cell(clamp.max_tenants)}</b><span>label_clamp (top-N + other)</span></div>
   </div>`;
  const cols = ["tenant","label","requests","prompt_tokens","generated_tokens",
                "cache_hit_tokens","kv_page_seconds","window_tokens",
                "quota_used_ratio"];
  // index-based handler lookup: a tenant id is attacker-influenced
  // (user emails), and interpolating it into an onclick JS string would
  // let a quote in the id break out (the HTML parser decodes esc()'s
  // entities BEFORE the JS engine parses the attribute)
  tenantRows = usage.tenants || [];
  const body = tenantRows.map((t, i) =>
    "<tr>" + cols.map(c => `<td>${
      c === "quota_used_ratio" || c === "kv_page_seconds" ? fnum(t[c]) : cell(t[c])
    }</td>`).join("")
    + `<td><button class="act" onclick="tenantSlo(${i})">slo</button></td></tr>`
  ).join("");
  let table = body ? `<br><h3>ledger (cumulative since boot)</h3><table><tr>`
    + cols.map(c => `<th>${esc(c)}</th>`).join("") + `<th></th></tr>${body}</table>` : "";
  const rcols = ["tenant","window_start","window_end","requests",
                 "prompt_tokens","generated_tokens","cache_hit_tokens",
                 "kv_page_seconds"];
  const rbody = (usage.rollups || []).slice(0, 24).map(r =>
    "<tr>" + rcols.map(c => `<td>${
      c === "window_start" || c === "window_end"
        ? esc(new Date((r[c]||0)*1000).toISOString().slice(11,19)) : cell(r[c])
    }</td>`).join("") + "</tr>").join("");
  if (rbody) table += `<br><h3>recent rollups (tenant_usage table)</h3><table><tr>`
    + rcols.map(c => `<th>${esc(c)}</th>`).join("") + `</tr>${rbody}</table>`;
  document.getElementById("view").innerHTML = cards + table
    + `<pre id="tenant-slo" class="kv"></pre>`;
  document.getElementById("status").textContent = "tenant usage metering";
}
let tenantRows = [];
async function tenantSlo(i){
  // the tenant's assigned SLO class, evaluated over ITS label slice
  const row = tenantRows[i];
  if (!row) return;
  const r = await fetch("/admin/slo?window=admin-ui&tenant=" + encodeURIComponent(row.tenant));
  const el = document.getElementById("tenant-slo");
  if (!r.ok){ el.textContent = "slo fetch failed: " + r.status; return; }
  const s = await r.json();
  el.textContent = JSON.stringify({tenant: s.tenant, slo_class: s.slo_class,
    tenant_label: s.tenant_label, clamped: s.tenant_clamped, ok: s.ok,
    objectives: (s.objectives||[]).map(o => ({name: o.name, target_ms: o.target_ms,
      window_p_ms: o.window_p_ms, window_samples: o.window_samples,
      burn_rate: o.burn_rate, ok: o.ok}))}, null, 1);
}
async function poolAct(rid, action){
  const r = await fetch(`/admin/engine/pool/${rid}/${action}`, {method:"POST"});
  document.getElementById("status").textContent = r.ok
    ? `replica ${rid} ${action} ok` : `replica ${rid} ${action} failed: ${r.status}`;
  if (r.ok) show("engine");
}
async function engineProfileCtl(action){
  const url = action === "start" ? "/admin/engine/profile/start"
                                 : "/admin/engine/profile/stop";
  const r = await fetch(url, {method:"POST"});
  document.getElementById("status").textContent =
    r.ok ? "profile " + action + " ok" : "profile " + action + " failed: " + r.status;
}
async function engineProfileStatus(){
  const r = await fetch("/admin/engine/profile/status");
  document.getElementById("status").textContent = r.ok
    ? "profiler active: " + (await r.json()).active
    : "profile status failed: " + r.status;
}
async function renderDiagnostics(){
  // system-scale counters + operation timing + support-bundle download
  const v = document.getElementById("view");
  const [sr, pr, cr] = await Promise.all([
    fetch("/admin/system/stats"), fetch("/admin/performance"),
    fetch("/admin/classification")]);
  if (!sr.ok){ v.textContent = "system stats fetch failed: " + sr.status; return; }
  const stats = await sr.json();
  let html = "";
  for (const family of ["users","teams","tokens","metrics","security","workflows"]){
    const fam = stats[family];
    if (!fam || typeof fam !== "object") continue;
    const cards = Object.keys(fam).map(k =>
      `<div class="card"><b>${cell(fam[k])}</b><span>${esc(family+"."+k)}</span></div>`).join("");
    html += `<div class="cards">${cards}</div>`;
  }
  const ent = stats.entities || {};
  const entRows = Object.keys(ent).map(k => {
    const e = ent[k];
    const total = (e && typeof e === "object") ? e.total : e;
    const enabled = (e && typeof e === "object") ? e.enabled : "";
    return `<tr><td>${esc(k)}</td><td>${cell(total)}</td><td>${cell(enabled)}</td></tr>`;
  }).join("");
  html += `<table><tr><th>entity</th><th>total</th><th>enabled</th></tr>${entRows}</table>`;
  if (pr.ok){
    const perf = await pr.json();
    const ops = perf.operations || {};
    const perfRows = Object.keys(ops).map(k => {
      const o = ops[k];
      return `<tr><td>${esc(k)}</td><td>${cell(o.count)}</td><td>${cell(o.avg_ms)}</td>`
        + `<td>${cell(o.p50_ms)}</td><td>${cell(o.p95_ms)}</td><td>${cell(o.p99_ms)}</td>`
        + `<td>${cell(o.max_ms)}</td><td>${cell(o.slow)}</td></tr>`;
    }).join("");
    html += `<br><b>operation timings</b><table><tr><th>operation</th><th>count</th>`
      + `<th>avg ms</th><th>p50</th><th>p95</th><th>p99</th><th>max</th><th>slow</th></tr>`
      + `${perfRows}</table>`
      + `<button class="act danger" onclick="clearPerf()">reset timings</button> `;
  }
  if (cr.ok){  // 404 when hot/cold classification is disabled
    const cls = await cr.json();
    html += `<br><b>gateway polling</b><div class="cards">`
      + `<div class="card"><b>${cell((cls.hot||[]).length)}</b><span>hot peers</span></div>`
      + `<div class="card"><b>${cell((cls.cold||[]).length)}</b><span>cold peers</span></div>`
      + `<div class="card"><b>${cell((cls.metadata||{}).cycle)}</b><span>poll cycle</span></div></div>`;
  }
  html += `<br><a class="act" href="/admin/support-bundle" download>download support bundle</a>`;
  v.innerHTML = html;
  document.getElementById("status").textContent = "diagnostics";
}
async function clearPerf(){
  const r = await fetch("/admin/performance", {method:"DELETE"});
  await renderDiagnostics();  // re-render first: it overwrites the status
  document.getElementById("status").textContent =
    r.ok ? "timings cleared" : "clear failed: " + r.status;
}
async function engineProfile(){
  const r = await fetch("/admin/engine/profile", {method:"POST",
    headers:{"content-type":"application/json"}, body:"{}"});
  document.getElementById("status").textContent =
    r.ok ? "profile captured" : "profile failed: " + r.status;
}
async function renderDashboard(){
  // totals from /metrics + hourly bars from the combined series (rollups
  // + un-rolled raw tail, so the current hour is never missing)
  const v = document.getElementById("view");
  const [mr, rr] = await Promise.all([fetch("/metrics"), fetch("/metrics/timeseries?hours=24")]);
  if (!mr.ok || !rr.ok){ v.textContent = "dashboard fetch failed"; return; }
  const metrics = await mr.json(), roll = await rr.json();
  const tools = metrics.tools || [];
  const calls = tools.reduce((a,t)=>a+(t.calls||0),0);
  const errors = tools.reduce((a,t)=>a+(t.errors||0),0);
  const avg = tools.length ? tools.reduce((a,t)=>a+(t.avg_ms||0),0)/tools.length : 0;
  const byHour = {};
  for (const r of roll) {
    const h = r.hour;
    byHour[h] = byHour[h] || {calls:0, errors:0};
    byHour[h].calls += r.calls ?? r.count ?? 0;
    byHour[h].errors += r.errors || 0;
  }
  const hours = Object.keys(byHour).map(Number).sort((a,b)=>a-b);
  const peak = Math.max(1, ...hours.map(h=>byHour[h].calls));
  const chart = hours.map(h=>{
    const b = byHour[h];
    const hv = Math.round((b.calls/peak)*100);
    const he = Math.round((b.errors/peak)*100);
    const label = new Date(h*3600*1000).getUTCHours();
    return `<div class="col" title="${b.calls} calls / ${b.errors} errors">`
      + `<div class="e" style="height:${he}%"></div>`
      + `<div class="v" style="height:${Math.max(hv-he,0)}%"></div>`
      + `<div class="t">${label}</div></div>`;
  }).join("");
  v.innerHTML = `<div class="cards">
    <div class="card"><b>${calls}</b><span>tool calls</span></div>
    <div class="card"><b>${errors}</b><span>errors</span></div>
    <div class="card"><b>${Math.round(avg*100)/100}</b><span>avg ms</span></div>
    <div class="card"><b>${tools.length}</b><span>active tools</span></div>
   </div>
   <div class="chart">${chart || '<span style="color:#889">no rollup data — POST /metrics/rollup to aggregate</span>'}</div>
   <br><button class="act" onclick="runRollup()">run rollup now</button>
   <button class="act" onclick="pruneMetrics()">prune raw metrics</button>
   <button class="act danger" onclick="resetMetrics()">reset ALL metrics (/metrics/reset)</button>`;
  document.getElementById("status").textContent = "dashboard";
}
async function runRollup(){
  const r = await fetch("/metrics/rollup", {method:"POST"});
  document.getElementById("status").textContent = r.ok ? "rolled up" : "rollup failed";
  renderDashboard();
}
async function resetMetrics(){
  if (!confirm("drop ALL raw metrics and rollups?")) return;
  const r = await fetch("/metrics/reset", {method:"POST"});
  document.getElementById("status").textContent = r.ok ? "metrics reset" : "reset failed";
  renderDashboard();
}
async function pruneMetrics(){
  const r = await fetch("/metrics/prune", {method:"POST"});
  document.getElementById("status").textContent = r.ok ?
    "pruned " + (await r.json()).pruned + " rows" : "prune failed";
}
let chatSession = null;
function renderChat(){
  document.getElementById("view").innerHTML = `
   <div style="background:#fff;padding:14px;box-shadow:0 1px 3px rgba(0,0,0,.08)">
    <b>llmchat playground</b> (tpu_local agent + gateway tools, SSE streaming)<br>
    <div id="chat-log" style="min-height:160px;max-height:420px;overflow:auto;
      font-size:13px;margin:10px 0;border:1px solid #eceef1;padding:8px"></div>
    <input id="chat-input" style="width:70%;padding:6px 10px;border:1px solid #ccd;border-radius:4px"
      placeholder="message…" onkeydown="if(event.key==='Enter')sendChat()">
    <button class="act" onclick="sendChat()">send (/llmchat)</button>
    <button class="act danger" onclick="resetChat()">reset session</button>
   </div>`;
  document.getElementById("status").textContent =
    chatSession ? "session " + chatSession : "no session yet";
}
function chatLine(cls, text){
  const log = document.getElementById("chat-log");
  if (!log) return null;  // user left the chat tab mid-stream
  const div = document.createElement("div");
  div.style.whiteSpace = "pre-wrap";
  if (cls === "user") div.style.fontWeight = "600";
  if (cls === "tool") div.style.color = "#667";
  if (cls === "err") div.style.color = "#a12622";
  div.textContent = text;
  log.appendChild(div);
  log.scrollTop = log.scrollHeight;
  return div;
}
async function resetChat(){
  if (chatSession) await fetch(`/llmchat/${chatSession}`, {method:"DELETE"});
  chatSession = null;
  renderChat();
}
let chatBusy = false;
async function sendChat(){
  if (chatBusy) return;  // one in-flight turn per session: concurrent
                         // turns would interleave the stored history
  const input = document.getElementById("chat-input");
  const text = input.value.trim();
  if (!text) return;
  chatBusy = true;
  try {
    if (!chatSession){
      const r = await fetch("/llmchat/connect", {method:"POST",
        headers:{"content-type":"application/json"}, body:"{}"});
      if (!r.ok){ chatLine("err", "connect failed: " + r.status); return; }
      chatSession = (await r.json()).session_id;
      document.getElementById("status").textContent = "session " + chatSession;
    }
    chatLine("user", "you: " + text);
    const r = await fetch(`/llmchat/${chatSession}/chat`, {method:"POST",
      headers:{"content-type":"application/json"},
      body: JSON.stringify({message: text, stream: true})});
    if (!r.ok){ chatLine("err", "chat failed: " + r.status); return; }
    input.value = "";  // only a SENT message clears the box
    const reader = r.body.getReader();
    const decoder = new TextDecoder();
    let buffer = "", tokenDiv = null;
    while (true){
      const {done, value} = await reader.read();
      if (done) break;
      buffer += decoder.decode(value, {stream: true});
      let idx;
      while ((idx = buffer.indexOf("\n\n")) !== -1){
        const frame = buffer.slice(0, idx);
        buffer = buffer.slice(idx + 2);
        if (!frame.startsWith("data: ") || frame === "data: [DONE]") continue;
        let event;
        try { event = JSON.parse(frame.slice(6)); } catch(e){ continue; }
        if (event.type === "token"){
          if (!tokenDiv) tokenDiv = chatLine("", "assistant: ");
          if (tokenDiv) tokenDiv.textContent += event.text;
        } else if (event.type === "tool_call"){
          tokenDiv = null;  // next step's tokens open a NEW line (they
                            // must render BELOW the tool lines, in order)
          chatLine("tool", `→ tool ${event.tool}(${event.arguments || "{}"})`);
        } else if (event.type === "tool_result"){
          chatLine("tool", `← ${event.tool}: ${event.text}`);
        } else if (event.type === "answer"){
          if (tokenDiv) tokenDiv = null;
          else chatLine("", "assistant: " + event.text);
        } else if (event.type === "error"){
          chatLine("err", "error: " + event.message);
        }
      }
    }
  } finally {
    chatBusy = false;
  }
}
function renderExportImport(){
  document.getElementById("view").innerHTML = `
   <div style="background:#fff;padding:14px;box-shadow:0 1px 3px rgba(0,0,0,.08)">
    <b>export</b><br>
    <label style="font-size:12px"><input type="checkbox" id="exp-secrets"> include secrets (sealed)</label>
    <button class="act" onclick="doExport()">download bundle (/export)</button>
    <hr>
    <b>import</b> (paste a bundle)<br>
    <textarea id="import-area" placeholder='{"version":1,"entities":{...}}'></textarea><br>
    <label style="font-size:12px"><input type="checkbox" id="imp-overwrite"> overwrite existing</label>
    <button class="act" onclick="doImport()">import (/import)</button>
    <pre id="imp-result" class="kv"></pre>
   </div>`;
  document.getElementById("status").textContent = "export / import";
}
async function doExport(){
  const secrets = document.getElementById("exp-secrets").checked;
  const r = await fetch("/export" + (secrets ? "?include_secrets=true" : ""));
  if (!r.ok){ document.getElementById("status").textContent = "export failed: " + r.status; return; }
  const blob = new Blob([JSON.stringify(await r.json(), null, 1)], {type:"application/json"});
  const a = document.createElement("a");
  a.href = URL.createObjectURL(blob); a.download = "mcpforge-export.json"; a.click();
  URL.revokeObjectURL(a.href);
}
async function doImport(){
  let bundle;
  try { bundle = JSON.parse(document.getElementById("import-area").value); }
  catch(e){ document.getElementById("status").textContent = "bad JSON: " + esc(String(e)); return; }
  const overwrite = document.getElementById("imp-overwrite").checked;
  const r = await fetch("/import", {method:"POST",
    headers:{"content-type":"application/json"},
    body: JSON.stringify({bundle, overwrite})});
  const out = await r.text();
  document.getElementById("imp-result").textContent = out.slice(0, 2000);
  document.getElementById("status").textContent = r.ok ? "imported" : "import failed: " + r.status;
}
function render(){
  const t = TABS[current];
  if (!t.cols) return;  // special tabs (engine/dashboard/chat/diagnostics/
                        // ingress/exportimport) render at fetch time
  const q = document.getElementById("q").value.toLowerCase();
  // `shown` is the single source of truth for row indices: click handlers
  // index into it, so a filter edit between render and click cannot
  // misresolve, and attacker data never lands inside a JS string
  shown = rows.filter(d => !q || JSON.stringify(d).toLowerCase().includes(q));
  document.getElementById("status").textContent = shown.length + " rows";
  const hasActs = t.toggle || t.edit || t.del || t.detail || t.rowacts
    || t.special === "plugins";
  const head = "<tr>" + t.cols.map(c=>`<th>${c}</th>`).join("")
    + (hasActs ? "<th></th>" : "") + "</tr>";
  const bools = new Set(t.boolcols || []);
  const body = shown.map((d,i)=>{
    const cells = t.cols.map(c=>{
      if (t.tracecol === c) return `<td><a class="trace" onclick="trace(${i})">${cell(d[c])}</a></td>`;
      if (t.special === "plugins" && c === "mode")
        return `<td><select class="mode" onchange="setMode(${i}, this.value)">`
          + ["enforce","enforce_ignore_error","permissive","audit","disabled"].map(m =>
            `<option${m===d.mode?" selected":""}>${m}</option>`).join("") + "</select></td>";
      return `<td>${cell(d[c], bools.has(c))}</td>`;
    }).join("");
    let act = "";
    if (t.detail) act += `<button class="act" onclick="detailRow(${i})">view</button> `;
    if (t.toggle) act += `<button class="act" onclick="toggleRow(${i})">toggle</button> `;
    if (t.edit)   act += `<button class="act" onclick="editRow(${i})">edit</button> `;
    for (const [j, ra] of (t.rowacts || []).entries())
      act += `<button class="act" onclick="rowAct(${i},${j})">${ra.label}</button> `;
    if (t.del)    act += `<button class="act danger" onclick="delRow(${i})">delete</button>`;
    return "<tr>"+cells+(hasActs?`<td>${act}</td>`:"")+"</tr>";
  }).join("");
  document.getElementById("view").innerHTML = `<table>${head}${body}</table>`;
}
async function show(name, keepCursor){
  current = name;
  if (!keepCursor) cursor = null;
  document.getElementById("detail").style.display = "none";
  document.getElementById("form").style.display = "none";
  document.getElementById("newbtn").style.display = TABS[name].create ? "" : "none";
  document.getElementById("morebtn").style.display = "none";
  document.querySelectorAll("nav button").forEach(b=>b.classList.toggle("active", b.textContent===name));
  const t = TABS[name];
  const s = document.getElementById("status");
  s.textContent = "loading…";
  if (t.special === "dashboard") return renderDashboard();
  if (t.special === "exportimport") return renderExportImport();
  if (t.special === "chat") return renderChat();
  if (t.special === "diagnostics") return renderDiagnostics();
  try {
    let url = t.url;
    if (t.paged) {
      url += (url.includes("?") ? "&" : "?") + "limit=100";
      if (cursor) url += "&cursor=" + encodeURIComponent(cursor);
    }
    const r = await fetch(url, {headers: {accept: "application/json"}});
    if (!r.ok) { s.textContent = r.status + " " + esc(await r.text()); return; }
    let data = await r.json();
    if (t.special === "engine") return renderEngine(data);
    if (t.special === "gwflight") return renderGatewayFlight(data);
    if (t.special === "forensics") return renderForensics(data);
    if (t.special === "controller") return renderController(data);
    if (t.special === "tenants") return renderTenants(data);
    if (t.special === "ingress") return renderIngress(data);
    if (t.path) data = data[t.path] || [];
    if (data && !Array.isArray(data) && Array.isArray(data.items)){
      cursor = data.next_cursor;   // cursor-paged shape (pagination.py)
      document.getElementById("morebtn").style.display = cursor ? "" : "none";
      data = data.items;
    }
    rows = Array.isArray(data) ? data : [];
    render();
  } catch(e){ s.textContent = "error: " + esc(String(e)); }
}
function nextPage(){ if (cursor) show(current, true); }
function openForm(){
  const t = TABS[current];
  if (!t.create) return;
  const f = document.getElementById("form");
  f.style.display = "block";
  f.innerHTML = `<b>new ${esc(current)}</b><br>` + t.create.fields.map(x =>
    `<input id="f-${x.split(":")[0]}" placeholder="${x}">`).join("")
    + (t.create.testurl
       ? `<button class="act" onclick="testForm()">test connection</button>`
       : "")
    + `<button class="act" onclick="submitForm()">create</button>`
    + `<span id="f-probe"></span>`;
}
async function testForm(){
  // wizard step: dry-run the connectivity probe before committing
  const t = TABS[current];
  const body = {};
  for (const spec of t.create.fields){
    const x = spec.split(":")[0];
    const el = document.getElementById("f-" + x);
    if (el && el.value) body[x] = el.value;
  }
  const probe = document.getElementById("f-probe");
  probe.textContent = "probing…";
  const r = await fetch(t.create.testurl, {method: "POST",
    headers: {"content-type": "application/json"},
    body: JSON.stringify(body)});
  if (!r.ok){ probe.textContent = "probe failed: " + r.status; return; }
  const d = await r.json();
  probe.innerHTML = d.ok
    ? `<span class="pill ok">reachable</span> ${cell(d.latency_ms)}ms, `
      + `${cell(d.tool_count)} tools, caps: ${esc((d.capabilities||[]).join(", "))}`
    : `<span class="pill bad">unreachable</span> ${esc(d.error||"")}`;
}
async function submitForm(){
  const t = TABS[current];
  const body = {};
  for (const spec of t.create.fields){
    const [x, kind] = spec.split(":");
    const v = document.getElementById("f-"+x).value;
    if (!v) continue;
    if (kind === "int") body[x] = parseInt(v, 10);
    else if (kind === "csv") body[x] = v.split(",").map(s=>s.trim()).filter(Boolean);
    else if (kind === "json") { try { body[x] = JSON.parse(v); } catch(e) { body[x] = v; } }
    else body[x] = v;
  }
  const r = await fetch(t.create.url, {method:"POST",
    headers:{"content-type":"application/json"}, body: JSON.stringify(body)});
  document.getElementById("status").textContent = r.ok ? "created" :
    `create failed: ${r.status} ` + esc(await r.text());
  if (r.ok && t.create.reveal){
    // mint-once secrets (API tokens): shown a single time, never stored
    const out = await r.json();
    const d = document.getElementById("detail");
    d.style.display = "block";
    d.innerHTML = `<b>copy it now — it is not retrievable later</b>
      <div class="reveal">${esc(String(out[t.create.reveal] || ""))}</div>`;
  }
  if (r.ok) show(current, true);
}
async function setMode(i, mode){
  const row = shown[i];
  if (!row) return;
  const r = await fetch(`/plugins/${encodeURIComponent(row.name)}/mode`, {
    method:"POST", headers:{"content-type":"application/json"},
    body: JSON.stringify({mode})});
  document.getElementById("status").textContent = r.ok
    ? `mode of ${row.name} → ${mode}` : "mode change failed: " + r.status;
  if (!r.ok) show(current);
}
async function rowAct(i, j){
  const t = TABS[current], row = shown[i];
  if (!row) return;
  const ra = t.rowacts[j];
  const r = await fetch(ra.url(row[ra.key || t.idcol || "id"]), {method: ra.method});
  document.getElementById("status").textContent =
    `${ra.label}: ` + (r.ok ? "ok" : "failed " + r.status);
  if (ra.show && r.ok){
    const d = document.getElementById("detail");
    d.style.display = "block";
    d.innerHTML = `<b>${esc(ra.label)}</b><pre class="kv">`
      + esc(JSON.stringify(await r.json(), null, 1).slice(0, 4000)) + `</pre>`;
    return;
  }
  show(current);
}
async function renderIngress(data){
  const opts = (data.available || []).map(m =>
    `<option${m===data.mode?" selected":""}>${esc(m)}</option>`).join("");
  document.getElementById("view").innerHTML = `
   <div class="cards">
    <div class="card"><b>${esc(String(data.mode))}</b><span>active ingress</span></div>
    <div class="card"><b>${cell(data.version)}</b><span>version</span></div>
   </div><br>
   <select id="ingress-mode">${opts}</select>
   <button class="act" onclick="setIngress()">switch mode (POST /admin/ingress)</button>`;
  document.getElementById("status").textContent = "ingress mount";
}
async function setIngress(){
  const mode = document.getElementById("ingress-mode").value;
  const r = await fetch("/admin/ingress", {method:"POST",
    headers:{"content-type":"application/json"}, body: JSON.stringify({mode})});
  document.getElementById("status").textContent = r.ok ? "switched" : "switch failed: " + r.status;
  show(current);
}
async function toggleRow(i){
  const t = TABS[current];
  const row = shown[i];
  if (!row) return;
  const id = row[t.idcol || "id"];
  const r = await fetch(t.toggle(id), {method: "POST"});
  if (!r.ok) document.getElementById("status").textContent = "toggle failed: " + r.status;
  show(current);
}
async function detailRow(i){
  const t = TABS[current];
  const row = shown[i];
  if (!row) return;
  const id = row[t.idcol || "id"];
  const r = await fetch(t.detail(id));
  const d = document.getElementById("detail");
  d.style.display = "block";
  if (!r.ok){ d.textContent = "detail fetch failed: " + r.status; return; }
  const full = await r.json();
  const kv = Object.entries(full).map(([k,v]) =>
    `<tr><td><b>${esc(k)}</b></td><td>${cell(v)}</td></tr>`).join("");
  let extra = "";
  if (t.special === "teams"){
    // server data never lands inside a JS string literal: handlers take
    // indices and resolve id/email from detailTeam at click time
    detailTeam = {id: String(id), members: full.members || []};
    const members = detailTeam.members.map((m, midx) =>
      `<tr><td>${esc(m.user_email||"")}</td><td>${esc(m.role||"")}</td>
       <td><button class="act danger" onclick="removeMemberAt(${midx})">remove</button></td></tr>`).join("");
    extra = `<br><b>members</b><table class="kv">${members}</table>
      <input id="m-email" placeholder="email"><input id="m-role" placeholder="role (member)">
      <button class="act" onclick="addMember(detailTeam.id)">add member (/teams/{id}/members)</button>
      <button class="act" onclick="inviteMember(detailTeam.id)">invite (/teams/{id}/invitations)</button>
      <span id="invite-out" class="kv"></span>`;
  }
  if (t.special === "roles"){
    // same index-based pattern as teams: no server data in JS literals
    detailRole = {id: String(id), assignments: full.assignments || []};
    const rows = detailRole.assignments.map((a, aidx) =>
      `<tr><td>${esc(a.user_email||"")}</td><td>${esc(a.scope_id||"")}</td>
       <td><button class="act danger" onclick="revokeRoleAt(${aidx})">revoke</button></td></tr>`).join("");
    extra = `<br><b>assignments</b><table class="kv">${rows}</table>
      <input id="r-email" placeholder="user email"><input id="r-scope" placeholder="scope_id (team-scoped only)">
      <button class="act" onclick="assignRole()">assign (/rbac/users/{email}/roles)</button>
      <br><b>permission inspector</b><br>
      <input id="p-email" placeholder="user email"><input id="p-perm" placeholder="permission">
      <button class="act" onclick="checkPermission()">check (/rbac/permissions/check)</button>
      <button class="act" onclick="userPermissions()">effective set</button>
      <span id="perm-out" class="kv"></span>`;
  }
  d.innerHTML = `<b>${esc(current)} ${esc(String(id))}</b>
    <table class="kv">${kv}</table>${extra}`;
}
let detailRole = null;  // {id, assignments[]} of the open roles detail pane
async function assignRole(){
  if (!detailRole) return;
  const email = document.getElementById("r-email").value;
  const scope = document.getElementById("r-scope").value;
  const r = await fetch(`/rbac/users/${encodeURIComponent(email)}/roles`, {
    method:"POST", headers:{"content-type":"application/json"},
    body: JSON.stringify({role_id: detailRole.id, scope_id: scope})});
  document.getElementById("status").textContent = r.ok ? "role assigned" :
    "assign failed: " + r.status + " " + esc(await r.text());
  show(current);
}
async function revokeRoleAt(aidx){
  if (!detailRole || !detailRole.assignments[aidx]) return;
  const a = detailRole.assignments[aidx];
  const email = String(a.user_email || "");
  const qs = a.scope_id ? `?scope_id=${encodeURIComponent(String(a.scope_id))}` : "";
  const r = await fetch(`/rbac/users/${encodeURIComponent(email)}/roles/${encodeURIComponent(detailRole.id)}` + qs,
    {method:"DELETE"});
  document.getElementById("status").textContent = r.ok ? "role revoked" :
    "revoke failed: " + r.status;
  show(current);
}
async function checkPermission(){
  const email = document.getElementById("p-email").value;
  const perm = document.getElementById("p-perm").value;
  const r = await fetch("/rbac/permissions/check", {method:"POST",
    headers:{"content-type":"application/json"},
    body: JSON.stringify({user_email: email, permission: perm})});
  const out = r.ok ? await r.json() : {error: r.status};
  document.getElementById("perm-out").textContent = JSON.stringify(out);
}
async function userPermissions(){
  const email = document.getElementById("p-email").value;
  const r = await fetch(`/rbac/permissions/user/${encodeURIComponent(email)}`);
  const out = r.ok ? await r.json() : {error: r.status};
  document.getElementById("perm-out").textContent = JSON.stringify(out);
}
async function addMember(teamId){
  const email = document.getElementById("m-email").value;
  const role = document.getElementById("m-role").value || "member";
  const r = await fetch(`/teams/${encodeURIComponent(teamId)}/members`, {
    method:"POST", headers:{"content-type":"application/json"},
    body: JSON.stringify({email, role})});
  document.getElementById("status").textContent = r.ok ? "member added" :
    "add failed: " + r.status + " " + esc(await r.text());
}
async function inviteMember(teamId){
  const email = document.getElementById("m-email").value;
  const r = await fetch(`/teams/${encodeURIComponent(teamId)}/invitations`, {
    method:"POST", headers:{"content-type":"application/json"},
    body: JSON.stringify({email})});
  if (r.ok){
    const out = await r.json();
    document.getElementById("invite-out").textContent =
      "invitation token: " + (out.token || "");
  } else document.getElementById("status").textContent = "invite failed: " + r.status;
}
let detailTeam = null;  // {id, members[]} of the open teams detail pane
async function removeMemberAt(midx){
  if (!detailTeam || !detailTeam.members[midx]) return;
  await removeMember(detailTeam.id, String(detailTeam.members[midx].user_email||""));
}
async function removeMember(teamId, email){
  const r = await fetch(`/teams/${encodeURIComponent(teamId)}/members/${encodeURIComponent(email)}`,
    {method:"DELETE"});
  document.getElementById("status").textContent = r.ok ? "member removed" :
    "remove failed: " + r.status;
}
let editTarget = null;  // id captured at OPEN time: a filter edit must not
                        // re-point the save at a different row
function editRow(i){
  const t = TABS[current];
  const row = shown[i];
  if (!row) return;
  editTarget = row[t.idcol || "id"];
  const d = document.getElementById("detail");
  d.style.display = "block";
  d.innerHTML = `<b>edit ${esc(String(editTarget))}</b><br>`
    + `<textarea id="edit-area"></textarea><br>`
    + `<button class="act" onclick="saveEdit()">save (PUT)</button>`;
  document.getElementById("edit-area").value = JSON.stringify(row, null, 1);
}
async function saveEdit(){
  const t = TABS[current];
  if (editTarget == null) return;
  let body;
  try { body = JSON.parse(document.getElementById("edit-area").value); }
  catch(e){ document.getElementById("status").textContent = "bad JSON: " + esc(String(e)); return; }
  const r = await fetch(t.edit(editTarget), {method:"PUT",
    headers:{"content-type":"application/json"}, body: JSON.stringify(body)});
  document.getElementById("status").textContent = r.ok ? "saved" :
    `save failed: ${r.status} ` + esc(await r.text());
  if (r.ok) show(current);
}
async function delRow(i){
  const t = TABS[current];
  const row = shown[i];
  if (!row || !confirm("delete " + (row.name || row[t.idcol || "id"]) + "?")) return;
  const r = await fetch(t.del(row[t.idcol || "id"]), {method:"DELETE"});
  if (!r.ok) document.getElementById("status").textContent = "delete failed: " + r.status;
  show(current);
}
async function trace(i){
  const t = TABS[current];
  const row = shown[i];
  if (!row) return;
  const id = encodeURIComponent(String(row[t.tracecol] || ""));
  const r = await fetch(`/admin/traces/${id}`);
  const d = document.getElementById("detail");
  d.style.display = "block";
  if (!r.ok) { d.textContent = "trace fetch failed: " + r.status; return; }
  const tree = await r.json();
  const spans = tree.spans;
  const byParent = {};
  for (const s of spans) (byParent[s.parent_span_id || ""] ??= []).push(s);
  const lines = [];
  const walk = (pid, depth) => {
    for (const s of byParent[pid] || []) {
      const cls = s.status === "ERROR" ? " err" : "";
      lines.push(`<div class="span-row${cls}">${"  ".repeat(depth)}${esc(s.name)}`
        + `  ${s.duration_ms == null ? "" : Math.round(s.duration_ms*100)/100 + "ms"}`
        + `  ${esc(JSON.stringify(s.attributes||{})).slice(0,160)}</div>`);
      walk(s.span_id, depth+1);
    }
  };
  walk("", 0);
  // orphan spans (parent outside the stored window) still render
  const seen = new Set(spans.map(s=>s.span_id));
  for (const s of spans)
    if (s.parent_span_id && !seen.has(s.parent_span_id))
      lines.push(`<div class="span-row">${esc(s.name)} (orphan)</div>`);
  // gantt: bars positioned over the trace window (reference trace timeline)
  let gantt = "";
  const starts = spans.map(s=>s.start_ts).filter(v=>v!=null);
  if (starts.length){
    const t0 = Math.min(...starts);
    const t1 = Math.max(...spans.map(s=>(s.start_ts||t0)+((s.duration_ms||0)/1000)));
    const window_s = Math.max(t1 - t0, 1e-6);
    gantt = "<br><b>timeline</b>" + spans.map(s=>{
      const left = (((s.start_ts||t0)-t0)/window_s)*100;
      const width = Math.max((((s.duration_ms||0)/1000)/window_s)*100, 0.3);
      const cls = s.status === "ERROR" ? "bar err" : "bar";
      return `<div class="gantt"><span class="lbl">${esc(s.name)}</span>`
        + `<div class="${cls}" style="left:${left.toFixed(2)}%;width:${width.toFixed(2)}%"></div></div>`;
    }).join("");
  }
  d.innerHTML = `<b>trace ${esc(id)}</b> — ${spans.length} spans` + lines.join("") + gantt;
}
function autoRefresh(){
  if (timer) { clearInterval(timer); timer = null; }
  if (document.getElementById("auto").checked) timer = setInterval(()=>show(current), 5000);
}
const nav = document.getElementById("nav");
for (const name of Object.keys(TABS)){
  const b = document.createElement("button");
  b.textContent = name; b.onclick = ()=>show(name); nav.appendChild(b);
}
show("tools");
"""



def setup_admin_ui(app: web.Application) -> None:
    async def admin_page(request: web.Request) -> web.Response:
        request["auth"].require("observability.read")
        response = web.Response(text=_PAGE, content_type="text/html")
        # double-submit CSRF: the page JS echoes this cookie's value in
        # X-CSRF-Token on every mutating fetch (csrf_middleware validates)
        settings = request.app["ctx"].settings
        if settings.csrf_enabled:
            from ..services import csrf_service
            token = csrf_service.mint(request["auth"].user,
                                      settings.jwt_secret_key,
                                      ttl_s=settings.csrf_token_ttl_s)
            response.set_cookie(settings.csrf_cookie_name, token,
                                httponly=False,  # JS must read to echo
                                secure=settings.csrf_cookie_secure,
                                samesite="Strict", path="/")
        return response

    # substitute the configured CSRF names ONCE (settings are fixed for
    # the app's lifetime; the cookie name lands inside a JS regex literal,
    # so regex metacharacters in it must be escaped — 'csrf.token' is a
    # valid RFC 6265 name that would otherwise change the pattern)
    import re as _re
    settings = app["ctx"].settings
    _served_js = _JS.replace(
        "csrf_token=", _re.escape(settings.csrf_cookie_name) + "=").replace(
        '"X-CSRF-Token"', '"' + settings.csrf_header_name + '"')

    async def admin_js(request: web.Request) -> web.Response:
        request["auth"].require("observability.read")
        return web.Response(text=_served_js,
                            content_type="application/javascript")

    app.router.add_get("/admin", admin_page)
    app.router.add_get("/admin/", admin_page)
    app.router.add_get("/admin/app.js", admin_js)


def admin_page_source() -> str:
    """HTML + JS combined, for the UI contract/coverage test tier (the
    gates scan every URL the page's JS can build)."""
    return _PAGE + _JS


def admin_js_source() -> str:
    """The JS module alone, for the execution test tier
    (tests/integration/test_admin_js_render.py)."""
    return _JS
