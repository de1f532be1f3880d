"""Tokens per second of the window: the prompt tokens of requests whose
first token came in it, plus every output token emitted in it, over its
length."""


def read(run):
    s = run.served
    prompt = sum(r.prompt_len for r in s.records
                 if r.times and s.w0 <= r.times[0] < s.w1)
    out = sum(1 for r in s.records for t in r.times if s.w0 <= t < s.w1)
    return (prompt + out) / (s.w1 - s.w0)
