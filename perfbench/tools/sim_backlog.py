#!/usr/bin/env python3
"""Why ``served_tokens_per_s`` is counted between prompt completions: a
model of the scheduler under the backlog cell's traffic, on the CPU, no
device involved and no device number produced.

One iteration prefills one 64-token chunk of the oldest waiting request
and decodes one token for every request past its prefill; 8 callers in a
closed loop; prompts uniform 384-960, replies 16-64, the same multiset in
every seed; 1 % noise on an iteration's time. For 24 seeds it prints the
spread of six runs (quartile distance over the median) of three ways to
count a rate in a window: between request completions, between prompt
completions with generated tokens credited on arrival (what the benchmark
does, ``harness/stats.py``), and requests completed inside the window
over its length. PERF.md, section 2, quotes the result.

    python3 perfbench/tools/sim_backlog.py
"""

import statistics

import numpy as np


def sim(seed, window=51.0, clients=8, pool=64, start_after=2, chunk=64, t_dec=0.162, t_pre=0.072, host=0.012):
    rng = np.random.default_rng(seed)
    u = (np.arange(pool) + 0.5) / pool
    P = np.round(384 + u * (960 - 384)).astype(int); O = np.round(16 + u * (64 - 16)).astype(int)
    P = P[rng.permutation(pool)]; O = O[rng.permutation(pool)]
    nxt = 0; t = 0.0
    waiting = []; running = []  # dict per req
    events = []  # (time, kind, req)
    def new(i, t):
        return dict(id=i, p=P[i % pool], o=O[i % pool], done_p=0, gen=0, sub=t)
    for c in range(clients):
        waiting.append(new(nxt, 0.0)); nxt += 1
    comps = []; firsts = []; tokev = []
    while t < 400:
        pre = next((r for r in waiting), None)
        did_pre = False
        if pre is not None:
            n = min(chunk, pre['p'] - pre['done_p']); pre['done_p'] += n; did_pre = True
        dec = list(running)
        dt = host + (t_pre if did_pre else 0) + (t_dec if dec or True else 0)
        dt *= 1 + 0.01 * rng.standard_normal()
        t += dt
        for r in dec:
            r['gen'] += 1; tokev.append(t)
            if r['gen'] >= r['o']:
                running.remove(r); comps.append((t, r['p'] + r['o'])); waiting.append(new(nxt, t)); nxt += 1
        if did_pre and pre['done_p'] >= pre['p']:
            waiting.remove(pre); pre['gen'] = 1; tokev.append(t); firsts.append((t, pre['p'])); running.append(pre)
    return comps, firsts, tokev
def rate_between_completions(comps, t0, t1):
    inside = [(t, n) for t, n in comps if t0 <= t <= t1]
    return sum(n for t, n in inside[1:]) / (inside[-1][0] - inside[0][0]), len(inside)
def rate_between_firsts(firsts, tokev, t0, t1):
    ins = [(t, n) for t, n in firsts if t0 <= t <= t1]
    ta, tb = ins[0][0], ins[-1][0]
    return (sum(n for t, n in ins[1:]) + sum(1 for t in tokev if ta < t <= tb)) / (tb - ta)
def iqr_share(v):
    q = statistics.quantiles(v, n=4); return (q[2] - q[0]) / statistics.median(v)
def main():
    for W in (51, 30):
        A = []; B = []; C=[]
        for seed in range(24):
            comps, firsts, tokev = sim(seed)
            t0 = comps[1][0]  # window starts at second completion
            a, n = rate_between_completions(comps, t0, t0 + W); A.append(a)
            B.append(rate_between_firsts(firsts, tokev, t0, t0 + W))
            t0f = 20.0
            inside = [(t, n) for t, n in comps if t0f <= t <= t0f + W]
            C.append(sum(n for t, n in inside) / W)
        for name, v in (("between completions", A), ("between first tokens", B), ("completed in window/W", C)):
            sets = [iqr_share(v[i:i+6]) for i in range(0, 24, 6)]
            print(W, name, "median", round(statistics.median(v), 1), "IQR share sets of 6:", [round(float(x) * 100, 2) for x in sets], "all", round(float(iqr_share(v)) * 100, 2))


if __name__ == "__main__":
    main()
