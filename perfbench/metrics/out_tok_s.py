"""out_tok_s: output tokens emitted inside the window over the window's
seconds (the first token of each request, from its prefill, included)."""


def read(run):
    toks = sum(len(r.generated) for r in run.requests)
    return toks / run.elapsed_s if toks else None
