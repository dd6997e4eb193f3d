"""Gateway + /v1 route: request sent by the client -> entry of engine.submit
(HTTP parse, auth, middleware, template render, tokenise), median, ms."""
from benchmark.harness.stats import percentile


def read(ctx):
    by_index = {r.index: r for r in ctx.records}
    spans = [(entered - by_index[i].sent) * 1e3
             for i, (entered, _req) in ctx.submits.items() if i in by_index]
    return percentile(spans, 50) if spans else None
