"""Rows that decoded a token per decode launch, over the traced window: the
tokens the decode launches produced over the engine's count of them."""


def read(run):
    s = run.served
    launches = (s.stats_w1["launches"]["decode"]
                - s.stats_w0["launches"]["decode"])
    if not launches:
        return None
    return sum(len(st["decode_kv"]) for st in s.steps) / launches
