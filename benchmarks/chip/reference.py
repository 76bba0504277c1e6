"""The plain reference: pattern semantics straight from the raw events.

This module imports nothing of the program.  It derives each query's
partial matches from the raw stream (who opens a window, which events a
match needs, what its binding is) and follows every partial match (PM)
alone, as if nothing were ever shed: a PM opened at event ``o`` sees
events ``o+1 .. o+ws-1``; under skip-till-next-match it advances on the
next event of the class it waits for (Q1), or on each new distinct
defender bound to its striker (Q3), and completes when it reaches the
final state.  Each PM's fate at the last processed event ``E`` is then
one of: completed (C), still open (L) or expired (X).

pSPICE's shedder may drop any PM before its fate; which ones it drops is
a policy, decided from utilities the program estimates, and not part of
these semantics.  What the semantics do fix, and what ``compare`` holds
the program to, with a limit of 0 on each number:

  events_gap        events the program processed != events offered
  admit_gap         events admitted != events the token bucket admits
  spawn_gap         PMs created (+ store overflow) != windows opened
  store_wrong       live PMs that are not a reference PM in its state
  unsound_matches   emitted matches that are not reference completions
  count_excess      completions counted above the reference's
  loss_unexplained  completions or open PMs missing beyond pms_shed
  shed_unexplained  pms_shed beyond the PMs that could have been shed
  refresh_missing   model refreshes due at the configured cadence that
                    did not run

and, with a limit set from readings of sound runs and of planted faults
(the configuration file's ``limits``; a number without one there is
reported but not compared):

  lost_per_found    completions the shedder lost per completion found:
                    what Algorithm 2 costs in quality
  table_gap         widest relative gap between the utility tables the
                    program deployed at its last refresh and the plain
                    fit (``fit_tables``) of the observations it had then
  latency_x_bound   the largest simulated event latency the program
                    reports in the window, over the configured bound:
                    what Algorithm 1 is for

Tenant lanes are checked one by one (``compare_lanes``): each lane's
outputs against its own raw stream, the exact numbers summed over lanes,
the three read numbers the worst lane's.
"""
from __future__ import annotations

import numpy as np

INF = np.iinfo(np.int64).max // 4
# The numbers read against limits set from readings (the rest are exact).
READ = ("lost_per_found", "table_gap", "latency_x_bound")


def _q1_pms(q: dict, type_id, attr, E: int):
    """Q1 seq(RE_1..RE_k) over rising quotes of the k pattern symbols."""
    k, ws = q["num_symbols"], q["window_size"]
    cls = np.where((type_id < k) & (attr == 1), type_id + 1, 0)
    opens = np.flatnonzero(cls[:E + 1] == 1).astype(np.int64)
    t = opens.copy()
    adv = []
    for c in range(2, k + 1):                   # state c-1 needs class c
        pos = np.flatnonzero(cls == c).astype(np.int64)
        if pos.size == 0:
            t = np.full_like(t, INF)
        else:
            i = np.searchsorted(pos, t, side="right")
            nxt = np.where(i < pos.size, pos[np.minimum(i, pos.size - 1)],
                           INF)
            t = np.where((t < INF) & (nxt - opens < ws), nxt, INF)
        adv.append(t)
    adv = np.stack(adv, axis=1)
    return dict(open=opens, bind=np.full(opens.size, -1, np.int64),
                adv=adv, final=k, ids=None)


def _q3_first_ids(type_id, attr, group, opens, ws: int, n: int,
                  block: int = 8192):
    """For each opener: the first index, inside its window, of each
    distinct defender id bound to its striker (INF where none)."""
    ids = np.unique(type_id[(attr == 1)])
    out = np.full((opens.size, ids.size), INF, np.int64)
    span = np.arange(1, ws, dtype=np.int64)
    for b0 in range(0, opens.size, block):
        o = opens[b0:b0 + block]
        j = o[:, None] + span[None, :]
        valid = j < n
        jc = np.minimum(j, n - 1)
        ok = valid & (attr[jc] == 1) & (group[jc] == group[o][:, None])
        tid = type_id[jc]
        for col, k in enumerate(ids):
            hit = ok & (tid == k)
            first = hit.argmax(axis=1)
            out[b0:b0 + block, col] = np.where(hit.any(axis=1),
                                               o + 1 + first, INF)
    return ids, out


def reference_pms(config: dict, type_id, attr, group, E: int) -> list:
    """Per query: every PM opened at or before ``E`` with its advance
    indices (``adv[:, s-1]`` = index of the step from state s)."""
    n = E + 1
    type_id, attr, group = type_id[:n], attr[:n], group[:n]
    out, cache = [], {}
    for q in config["queries"]:
        if q["query"] == "Q1":
            out.append(_q1_pms(q, type_id, attr, E))
            continue
        ws = q["window_size"]
        opens = np.flatnonzero(attr == 2).astype(np.int64)
        if ws not in cache:
            cache[ws] = _q3_first_ids(type_id, attr, group, opens, ws, n)
        ids, first = cache[ws]
        order = np.argsort(first, axis=1, kind="stable")
        srt = np.take_along_axis(first, order, axis=1)
        a = q["any_n"]
        adv = srt[:, :a] if srt.shape[1] >= a else np.concatenate(
            [srt, np.full((srt.shape[0], a - srt.shape[1]), INF)], axis=1)
        out.append(dict(open=opens, bind=group[opens].astype(np.int64),
                        adv=adv, final=a + 1, ids=(ids, first)))
    return out


def fates(config: dict, pms: list, E: int) -> list:
    """Per query: completion index, state and fate of each PM at ``E``."""
    res = []
    for q, pm in zip(config["queries"], pms):
        ws, adv = q["window_size"], pm["adv"]
        steps = adv.shape[1]
        comp = adv[:, -1] if steps else pm["open"]
        done = (comp <= E) & (comp - pm["open"] < ws)
        state = 1 + (adv <= E).sum(axis=1)
        alive = ~done & (E - pm["open"] < ws)
        res.append(dict(comp=np.where(done, comp, -1), state=state,
                        done=done, alive=alive,
                        expired=~done & ~alive))
    return res


def admitted(pushes: list, rate: float, burst: float) -> int:
    """Events a token bucket clocked by arrival time admits, where every
    push fits the bucket; -1 where a push would be thinned (the sample it
    keeps is random, so this reference cannot name it)."""
    tokens, clock, total = float(burst), None, 0
    for n, t_now in pushes:
        if clock is not None:
            tokens = min(float(burst), tokens + max(0.0, t_now - clock)
                         * rate)
        clock = t_now
        if n > tokens:
            return -1
        tokens = max(0.0, tokens - n)
        total += n
    return total


def num_states(q: dict) -> int:
    """States of a query's chain: Q1 one per symbol after the empty
    state; Q3 the empty state, the striker's, one per defender still
    needed, and the final state."""
    return q["num_symbols"] + 1 if q["query"] == "Q1" else q["any_n"] + 2


def _minmax(x, lo: float = 1e-6, hi: float = 1.0):
    span = x.max() - x.min()
    if span <= 0:
        return np.full_like(x, hi)
    return lo + (x - x.min()) / span * (hi - lo)


def fit_tables(config: dict, obs_counts, obs_rewards) -> list:
    """pSPICE's utility table of each query (paper Sec. III-B/C), fitted in
    float64 from transition counts and processing-time sums.

    The chain is the row-normalised counts (a row never seen stays put;
    the final state absorbs).  For ``j + 1`` bins of ``bin_size`` events
    left in the window: P is the final column of ``T ** ((j+1) * bs)``;
    tau is the expected processing time still to come, by value
    iteration with the final state at zero cost.  Both are min-max scaled
    to [1e-6, 1]; the utility is P / tau.  Returns per query a
    ``(bins, states)`` array."""
    e, out = config["engine"], []
    bs, c_match = e["bin_size"], e["cost"]["c_match"]
    for p, q in enumerate(config["queries"]):
        m = num_states(q)
        c = np.asarray(obs_counts[p, :m, :m], np.float64)
        rsum = np.asarray(obs_rewards[p, :m, :m], np.float64)
        row = c.sum(axis=1, keepdims=True)
        T = np.where(row > 0, c / np.maximum(row, 1e-30), np.eye(m))
        T[m - 1] = np.eye(m)[m - 1]
        R = np.where(c > 0, rsum / np.maximum(c, 1e-30), c_match)
        bins = max(1, -(-q["window_size"] // bs))
        step = np.linalg.matrix_power(T, bs)
        r = (T * R).sum(axis=1)
        r[m - 1] = 0.0
        Tn = T.copy()
        Tn[m - 1] = 0.0
        acc, tau = np.eye(m), np.zeros(m)
        P, tau_t = np.zeros((bins, m)), np.zeros((bins, m))
        for j in range(bins):
            acc = acc @ step
            P[j] = acc[:, -1]
            for _ in range(bs):
                tau = r + Tn @ tau
            tau_t[j] = tau
        out.append(_minmax(P) / np.maximum(_minmax(tau_t), 1e-6))
    return out


def table_gap(config: dict, deployed, obs_counts, obs_rewards) -> float:
    """Widest gap between the deployed tables and ``fit_tables``, over the
    entries a live PM can look up (its bins, the states between opening
    and completion), relative to the reference entry or, for entries
    below it, to a thousandth of the query's largest."""
    gap = 0.0
    for p, ref in enumerate(fit_tables(config, obs_counts, obs_rewards)):
        bins, m = ref.shape
        ref = ref[:, 1:m - 1]
        got = np.asarray(deployed[p, :bins, 1:m - 1], np.float64)
        floor = 1e-3 * np.abs(ref).max()
        rel = np.abs(got - ref) / np.maximum(np.abs(ref), floor)
        gap = max(gap, float(rel.max()))
    return gap


def compare(config: dict, raw_cols: tuple, n_offered: int, prog: dict,
            admission: int | None = None) -> tuple[dict, dict]:
    """The numbers that decide ``correct``, each as (value, limit), and
    the reference's own counts (information only)."""
    type_id, attr, group = raw_cols
    E = n_offered - 1
    pms = reference_pms(config, type_id, attr, group, E)
    fs = fates(config, pms, E)
    P = len(config["queries"])
    nums = dict(events_gap=abs(prog["events_processed"] - n_offered))
    if admission is not None:
        nums["admit_gap"] = (abs(prog["admitted"] - admission)
                             if admission >= 0 else n_offered)
    opens = np.array([pm["open"].size for pm in pms], np.int64)
    created = prog["pms_created"]
    nums["spawn_gap"] = int(abs(opens.sum() - created.sum()
                                - prog["overflow"])
                            + np.maximum(created - opens, 0).sum())
    store_wrong, lost, could_shed = 0, 0, 0
    unsound, count_excess, found_all, missed = 0, 0, 0, 0
    for p in range(P):
        pm, f = pms[p], fs[p]
        by_open = {int(o): i for i, o in enumerate(pm["open"])}
        live = np.flatnonzero(prog["active"][p])
        alive_ok = 0
        for s in live:
            i = by_open.get(int(prog["open_idx"][p, s]))
            ok = (i is not None and f["alive"][i]
                  and prog["bind"][p, s] == pm["bind"][i]
                  and prog["state"][p, s] == f["state"][i])
            if ok and pm["ids"] is not None:
                ids, first = pm["ids"]
                want = set(ids[first[i] <= E].tolist())
                have = set(int(x) for x in prog["idset"][p, s] if x >= 0)
                ok = want == have
            if ok:
                alive_ok += 1
            else:
                store_wrong += 1
        ref_c = {(int(o), int(b), int(e)) for o, b, e in zip(
            pm["open"][f["done"]], pm["bind"][f["done"]],
            f["comp"][f["done"]])}
        if prog["matches"] is not None:
            got = prog["matches"][p]
            unsound += len(got - ref_c)
            found = len(got & ref_c)
        else:
            found = int(prog["complex_count"][p])
        count_excess += max(0, int(prog["complex_count"][p]) - len(ref_c))
        found_all += min(found, len(ref_c))
        missed += len(ref_c) - min(found, len(ref_c))
        lost += (len(ref_c) - min(found, len(ref_c))
                 + int(f["alive"].sum()) - alive_ok)
        could_shed += int(f["expired"].sum())
    nums["store_wrong"] = store_wrong
    if prog["matches"] is not None:
        nums["unsound_matches"] = unsound
    nums["count_excess"] = count_excess
    shed = prog["pms_shed"] + prog["overflow"]
    nums["loss_unexplained"] = max(0, lost - shed)
    nums["shed_unexplained"] = max(0, shed - (lost + could_shed))
    every = config["served"]["refresh"]["every_chunks"]
    nums["refresh_missing"] = max(0, prog["chunks_run"] // every
                                  - prog["refresh_count"])
    checks = {k: (int(v), 0) for k, v in nums.items()}
    read = dict(
        lost_per_found=missed / max(found_all, 1),
        table_gap=table_gap(config, prog["ut_tables"], *prog["refresh_obs"]),
        latency_x_bound=prog["l_e_max"] / config["engine"]["latency_bound"])
    # A number is compared only once readings have set its limit; until
    # then it is reported beside the reference's counts.
    for k, v in read.items():
        if k in config["limits"]:
            checks[k] = (v, config["limits"][k])
    info = dict(opened=int(opens.sum()),
                completed=int(sum(f["done"].sum() for f in fs)),
                found=found_all,
                unlimited={k: v for k, v in read.items()
                           if k not in config["limits"]})
    return checks, info



def compare_lanes(configs: list, raws: list, n_offered: int, progs: list,
                  admissions: list) -> tuple[dict, dict, list]:
    """``compare`` on each lane alone, against that lane's own raw stream
    and configuration (``n_offered`` events each: lanes advance in
    lockstep).  Returns the checks over all lanes (exact numbers summed,
    the read numbers the worst lane's), the reference's counts summed,
    and each lane's own counts and checks.  One lane gives ``compare``'s
    own result."""
    checks, per_lane = {}, []
    for config, raw, prog, adm in zip(configs, raws, progs, admissions):
        c, info = compare(config, raw, n_offered, prog, adm)
        per_lane.append(dict(info, checks={k: v for k, (v, _) in
                                           c.items()}))
        for k, (v, lim) in c.items():
            if k in checks:
                old = checks[k][0]
                v = max(old, v) if k in READ else old + v
            checks[k] = (v, lim)
    info = {k: sum(p[k] for p in per_lane)
            for k in ("opened", "completed", "found")}
    info["unlimited"] = {k: max(p["unlimited"][k] for p in per_lane)
                         for k in per_lane[0]["unlimited"]}
    return checks, info, per_lane
