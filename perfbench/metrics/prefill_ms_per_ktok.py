"""prefill_ms_per_ktok: device time of the prefill programs and of the
page inserts that follow them, per 1000 prompt tokens prefilled, over the
traced window. The tokens are those the benchmark's ``bench.prefill``
spans carry, each paired with the program it launched."""
from perfbench import xplane


def read(run):
    tr = run.trace
    if tr is None:
        return None
    pre = xplane.paired(tr.spans_named("bench.prefill"),
                        tr.modules(0, "_prefill"))
    ins = xplane.paired(tr.spans_named("bench.insert"),
                        tr.modules(0, "_insert"))
    tokens = sum(int(s.stats.get("tokens", 0)) for s, _ in pre)
    if not pre or not tokens:
        return None
    ns = sum(m.dur for _, m in pre) + sum(m.dur for _, m in ins[:len(pre)])
    return ns * 1e-6 / (tokens / 1000.0)
