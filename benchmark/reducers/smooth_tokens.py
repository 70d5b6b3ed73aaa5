"""A steadier statistic beside `out_tokens_per_s`: tokens GENERATED in the
window, estimated from the same client stamps.

The engine hands the client a whole decode block's tokens at once (up to 64
per request, every second or two), so the count of tokens that ARRIVED in the
window swings by one block with the window's phase. Here each burst's tokens
are spread evenly over the time since the request's previous burst, which is
when the device computed them, and the part inside the window is counted. In
the long run both rates are the same; this one does not depend on where the
window's edges fall between two bursts. The first burst of a request (its
first token) counts where it arrived.
"""

def read(ctx, gap_s=0.05):
    head = ctx["stamps"]
    lo, hi = head["t0"], head["t0"] + head["seconds"]
    total = 0.0
    for r in head["requests"]:
        if r.get("error") or r.get("status") not in (200, None) or not r.get("chunks"):
            continue
        bursts = []  # [time of the burst's last chunk, tokens]
        for t in r["chunks"]:
            if bursts and t - bursts[-1][0] <= gap_s:
                bursts[-1][0] = t
                bursts[-1][1] += 1
            else:
                bursts.append([t, 1])
        prev = None
        for t, n in bursts:
            if prev is None:
                total += n if lo <= t <= hi else 0.0
            else:
                a, b = max(prev, lo), min(t, hi)
                if b > a:
                    total += n * (b - a) / (t - prev)
            prev = t
    return total / head["seconds"]
