"""The benchmark's generator of client-event days, from a seed.

The distribution is that of the program's ``data/loggen.py`` (a Markov
chain over activity states per session, each state emitting events of its
own namespace templates, Poisson events per step, exponential gaps with
occasional 30-minute splits, a signup funnel, partial time order within
64 chunks), drawn with whole-array operations instead of a loop per
event. The draws differ from loggen's; the distribution does not. The
benchmark keeps its own copy so that a change to the program cannot
change the traffic it is measured on.

A day is a dict of columns: ``name_id`` (int32, index into
``name_table()``), ``user_id``, ``session_id`` (the cookie), ``timestamp``
(ms), ``ip`` (int64) and ``code`` (int32, the frequency rank of the name
over the day, ties by name id: the dictionary coding of paper section 3).
Retry duplicates (rows equal in all five keys) sit right after their
original.
"""
from __future__ import annotations

import numpy as np

CLIENTS = ("web", "iphone", "android", "ipad")
CLIENT_WEIGHTS = (0.45, 0.25, 0.22, 0.08)
STATES = (
    "home_browse", "mentions", "search_flow", "profile_browse",
    "discover", "who_to_follow",
    "signup_start", "signup_form", "signup_follow", "signup_done",
    "exit",
)
_ST = {s: i for i, s in enumerate(STATES)}
STATE_EVENTS = {
    "home_browse": [
        ("home:timeline:stream:tweet:impression", 8.0),
        ("home:timeline:stream:tweet:click", 1.0),
        ("home:timeline:stream:avatar:profile_click", 0.5),
        ("home:timeline:stream:tweet:expand", 0.7),
        ("home:timeline::scroll_bar:scroll", 2.0),
    ],
    "mentions": [
        ("home:mentions:stream:tweet:impression", 4.0),
        ("home:mentions:stream:avatar:profile_click", 0.8),
        ("home:mentions:stream:tweet:reply", 0.6),
    ],
    "search_flow": [
        ("search:input:search_box:text:search_query", 2.0),
        ("search:results:stream:tweet:impression", 6.0),
        ("search:results:stream:tweet:click", 1.2),
        ("search:results:stream:user:follow", 0.3),
    ],
    "profile_browse": [
        ("profile:tweets:stream:tweet:impression", 5.0),
        ("profile:header:card:follow_button:follow", 0.6),
        ("profile:header:card:avatar:impression", 1.0),
    ],
    "discover": [
        ("discover:trends:list:trend:impression", 3.0),
        ("discover:trends:list:trend:click", 0.8),
        ("discover:stories:stream:story:impression", 2.0),
    ],
    "who_to_follow": [
        ("who_to_follow:suggestions:list:user:impression", 3.0),
        ("who_to_follow:suggestions:list:user:follow", 0.7),
        ("who_to_follow:suggestions:list:user:dismiss", 0.4),
    ],
    "signup_start": [("signup:landing:form:signup_button:click", 1.0)],
    "signup_form": [("signup:form:form:field:fill", 3.0),
                    ("signup:form:form:submit_button:submit", 1.0)],
    "signup_follow": [("signup:follow_suggestions:list:user:impression", 4.0),
                      ("signup:follow_suggestions:list:user:follow", 1.5)],
    "signup_done": [("signup:complete:page::impression", 1.0)],
    "exit": [("home:timeline::page:unload", 1.0)],
}
TRANSITIONS = {
    "home_browse": [("home_browse", 6.0), ("mentions", 1.0),
                    ("search_flow", 1.0), ("profile_browse", 0.8),
                    ("discover", 0.6), ("who_to_follow", 0.4), ("exit", 1.2)],
    "mentions": [("mentions", 3.0), ("home_browse", 1.5),
                 ("profile_browse", 1.0), ("exit", 0.8)],
    "search_flow": [("search_flow", 4.0), ("profile_browse", 1.2),
                    ("home_browse", 1.0), ("exit", 0.8)],
    "profile_browse": [("profile_browse", 3.0), ("home_browse", 1.5),
                       ("who_to_follow", 0.5), ("exit", 1.0)],
    "discover": [("discover", 3.0), ("search_flow", 1.0),
                 ("home_browse", 1.0), ("exit", 0.7)],
    "who_to_follow": [("who_to_follow", 2.0), ("profile_browse", 1.2),
                      ("home_browse", 1.0), ("exit", 0.6)],
    "signup_start": [("signup_form", 1.5), ("exit", 1.0)],
    "signup_form": [("signup_form", 1.0), ("signup_follow", 1.5),
                    ("exit", 1.0)],
    "signup_follow": [("signup_follow", 1.0), ("signup_done", 1.5),
                      ("exit", 0.8)],
    "signup_done": [("home_browse", 3.0), ("exit", 1.0)],
    "exit": [("exit", 1.0)],
}
DAY_MS = 86_400_000
USER_ID_BASE = 10 ** 12
USER_ID_STRIDE = 7_919


def name_table() -> list[str]:
    """Event names in id order: every client times every template."""
    return [f"{c}:{suffix}" for c in CLIENTS
            for events in STATE_EVENTS.values() for suffix, _ in events]


def stage_codes(funnel, code_of_name) -> list[np.ndarray]:
    """Per funnel stage, the codes of the names that end in its suffix
    (the stage pattern ``*:<suffix>`` over every client)."""
    names = name_table()
    return [np.array([code_of_name[i] for i, n in enumerate(names)
                      if n.endswith(":" + suffix)], np.int32)
            for suffix in funnel]


def _id_grid():
    """(state, client, template) -> name id, and per state the cumulative
    template weights (padded with 2.0, above any uniform draw)."""
    names = {n: i for i, n in enumerate(name_table())}
    width = max(len(v) for v in STATE_EVENTS.values())
    ids = np.zeros((len(STATES), len(CLIENTS), width), np.int32)
    cum = np.full((len(STATES), width), 2.0)
    for s, events in STATE_EVENTS.items():
        w = np.array([w for _, w in events])
        cum[_ST[s], :len(events)] = np.cumsum(w / w.sum())
        cum[_ST[s], len(events) - 1] = 1.0
        for c, client in enumerate(CLIENTS):
            ids[_ST[s], c, :len(events)] = [names[f"{client}:{suffix}"]
                                             for suffix, _ in events]
    return ids, cum


def _transition_cum() -> np.ndarray:
    t = np.zeros((len(STATES), len(STATES)))
    for a, pairs in TRANSITIONS.items():
        for b, w in pairs:
            t[_ST[a], _ST[b]] = w
    return (t / t.sum(axis=1, keepdims=True)).cumsum(axis=1)


def assign_codes(name_id: np.ndarray, n_names: int) -> np.ndarray:
    """code_of_name: names ranked by descending count, ties by name id."""
    counts = np.bincount(name_id, minlength=n_names)
    name_of_code = np.lexsort((np.arange(n_names), -counts))
    code_of_name = np.empty(n_names, np.int32)
    code_of_name[name_of_code] = np.arange(n_names, dtype=np.int32)
    return code_of_name


def _untie(key: np.ndarray, ts: np.ndarray) -> None:
    """Move ``ts`` forward by whole ms, in place, until no two rows of one
    ``key`` share a timestamp: the order of a session's events is then
    fixed by time alone, as a client's clock gives it."""
    while True:
        order = np.lexsort((ts, key))
        k, t = key[order], ts[order]
        tie = np.flatnonzero((k[1:] == k[:-1]) & (t[1:] == t[:-1])) + 1
        if not len(tie):
            return
        ts[order[tie]] += 1


def generate(cfg: dict, seed: int) -> dict[str, np.ndarray]:
    """The configuration's unit of traffic from ``seed``: one period of
    ``period_ms`` (default a whole day) of a shard that takes
    ``events_per_day`` events a day."""
    period = cfg.get("period_ms", DAY_MS)
    return generate_day(cfg["events_per_day"] * period // DAY_MS, seed,
                        cfg["day"], span_ms=period)


def generate_day(n_events: int, seed: int, p: dict,
                 span_ms: int = DAY_MS) -> dict[str, np.ndarray]:
    """Exactly ``n_events`` rows of one day, ``retry_share`` of them retry
    duplicates. ``p`` holds the day's distribution (the configuration's
    ``day`` block).

    With ``span_ms`` under a day the rows are the sessions that start in
    the first ``span_ms`` of a day: each user's session count and events
    are thinned by ``span_ms / DAY_MS``, which leaves every session's
    distribution as it is."""
    rng = np.random.default_rng(seed)
    n_dup = int(round(n_events * p["retry_share"]))
    n_base = n_events - n_dup
    share = span_ms / DAY_MS
    # 10% more users than the mean asks for, so the cut below always bites
    n_users = int(1.1 * n_base / (p["events_per_user"] * share)) + 8
    user_ids = (np.arange(n_users, dtype=np.int64) * USER_ID_STRIDE
                + USER_ID_BASE)
    ip_of_user = rng.integers(0, 2 ** 31, n_users, dtype=np.int64)

    n_sess = rng.poisson(p["sessions_per_user_mean"] * share, n_users)
    S = int(n_sess.sum())
    sess_user = np.repeat(np.arange(n_users), n_sess)
    sess_client = rng.choice(len(CLIENTS), S, p=CLIENT_WEIGHTS)
    cookie = user_ids[sess_user] * 17 + sess_client

    steps = p["max_steps"]
    cum_t = _transition_cum()
    states = np.empty((S, steps), np.int64)
    states[:, 0] = np.where(rng.random(S) < p["signup_fraction"],
                            _ST["signup_start"], _ST["home_browse"])
    for t in range(1, steps):
        u = rng.random(S)
        states[:, t] = (cum_t[states[:, t - 1]] < u[:, None]).sum(axis=1)
    alive = states != _ST["exit"]
    n_ev = rng.poisson(p["events_per_step_mean"], (S, steps)).clip(0, 6) \
        * alive
    n_ev[:, 0] = np.maximum(n_ev[:, 0], 1)
    sess_start = p["start_ts_ms"] + rng.integers(0, span_ms, S)

    flat = np.repeat(np.arange(S * steps), n_ev.ravel())
    si = flat // steps
    st = states.ravel()[flat]
    E = len(flat)
    if E < n_base:
        raise ValueError(f"the day drew {E} events, fewer than {n_base}")
    ids, cum_w = _id_grid()
    u = rng.random(E)
    j = (cum_w[st] <= u[:, None]).sum(axis=1)
    name_id = ids[st, sess_client[si], j]

    gap = rng.exponential(p["mean_gap_s"], E)
    split = rng.random(E) < p["long_gap_prob"]
    gap = gap + split * (1800.0 + rng.exponential(600.0, E))
    inc = (gap * 1000).astype(np.int64) + 1
    run = np.cumsum(inc)
    first = np.flatnonzero(np.r_[True, si[1:] != si[:-1]])
    base = np.repeat(run[first] - inc[first], np.diff(np.r_[first, E]))
    ts = sess_start[si] + run - base
    _untie(cookie[si], ts)

    # partial time order: shuffled within 64 chunks, then cut to n_base
    chunk = max(1, E // 64)
    order = np.lexsort((rng.random(E), np.arange(E) // chunk))[:n_base]
    day = dict(name_id=name_id[order].astype(np.int32),
               user_id=user_ids[sess_user[si[order]]],
               session_id=cookie[si[order]].astype(np.int64),
               timestamp=ts[order].astype(np.int64),
               ip=ip_of_user[sess_user[si[order]]])
    if n_dup:
        src = np.sort(rng.choice(n_base, n_dup, replace=False))
        at = src + np.arange(n_dup) + 1          # right after the original
        orig = np.ones(n_events, bool)
        orig[at] = False
        take = np.empty(n_events, np.int64)
        take[orig] = np.arange(n_base)
        take[at] = src
        day = {k: v[take] for k, v in day.items()}
    day["code"] = assign_codes(day["name_id"], len(name_table()))[
        day["name_id"]]
    return day
