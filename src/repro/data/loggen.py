"""Synthetic client-event generator.

Produces structured, *behaviourally plausible* client-event streams so the
downstream analytics reproduce the paper's phenomena: Zipf-distributed event
frequencies (the dictionary's variable-length coding needs a skewed
histogram to win), Markov user behaviour (n-gram models find temporal
signal), an embedded signup funnel with per-stage abandonment (§5.3), and
adjacent-event collocations (§5.4).

Generation is vectorized: a (sessions x steps) Markov chain over activity
states, each state emitting events from its own distribution over the
hierarchical namespace.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..core.events import EventBatch, NameTable

CLIENTS = ("web", "iphone", "android", "ipad")

# Activity states and their Markov transition structure. The signup funnel
# is a chain of states with decreasing continuation probability.
STATES = (
    "home_browse", "mentions", "search_flow", "profile_browse",
    "discover", "who_to_follow",
    "signup_start", "signup_form", "signup_follow", "signup_done",
    "exit",
)
_ST = {s: i for i, s in enumerate(STATES)}

# Per-state event templates: (page, section, component, element, action).
STATE_EVENTS: dict[str, list[tuple[str, float]]] = {
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
    "signup_form":  [("signup:form:form:field:fill", 3.0),
                     ("signup:form:form:submit_button:submit", 1.0)],
    "signup_follow": [("signup:follow_suggestions:list:user:impression", 4.0),
                      ("signup:follow_suggestions:list:user:follow", 1.5)],
    "signup_done": [("signup:complete:page::impression", 1.0)],
    "exit": [("home:timeline::page:unload", 1.0)],
}

# The signup funnel (paper §5.3) as namespace glob patterns over the event
# universe above, one per stage, in order.
SIGNUP_FUNNEL = (
    "*:signup:landing:form:signup_button:click",
    "*:signup:form:form:submit_button:submit",
    "*:signup:follow_suggestions:list:user:follow",
    "*:signup:complete:page::impression",
)

# Markov transitions (row-stochastic after normalization).
def _transition_matrix() -> np.ndarray:
    n = len(STATES)
    t = np.zeros((n, n))
    def set_(a, pairs):
        for b, w in pairs:
            t[_ST[a], _ST[b]] = w
    set_("home_browse", [("home_browse", 6.0), ("mentions", 1.0),
                         ("search_flow", 1.0), ("profile_browse", 0.8),
                         ("discover", 0.6), ("who_to_follow", 0.4),
                         ("exit", 1.2)])
    set_("mentions", [("mentions", 3.0), ("home_browse", 1.5),
                      ("profile_browse", 1.0), ("exit", 0.8)])
    set_("search_flow", [("search_flow", 4.0), ("profile_browse", 1.2),
                         ("home_browse", 1.0), ("exit", 0.8)])
    set_("profile_browse", [("profile_browse", 3.0), ("home_browse", 1.5),
                            ("who_to_follow", 0.5), ("exit", 1.0)])
    set_("discover", [("discover", 3.0), ("search_flow", 1.0),
                      ("home_browse", 1.0), ("exit", 0.7)])
    set_("who_to_follow", [("who_to_follow", 2.0), ("profile_browse", 1.2),
                           ("home_browse", 1.0), ("exit", 0.6)])
    # Signup funnel: ~60% continue at each stage (tunable abandonment).
    set_("signup_start", [("signup_form", 1.5), ("exit", 1.0)])
    set_("signup_form", [("signup_form", 1.0), ("signup_follow", 1.5),
                         ("exit", 1.0)])
    set_("signup_follow", [("signup_follow", 1.0), ("signup_done", 1.5),
                           ("exit", 0.8)])
    set_("signup_done", [("home_browse", 3.0), ("exit", 1.0)])
    set_("exit", [("exit", 1.0)])
    return t / t.sum(axis=1, keepdims=True)


@dataclass
class LogGenConfig:
    n_users: int = 500
    sessions_per_user_mean: float = 3.0
    max_steps: int = 48                  # Markov steps per session
    events_per_step_mean: float = 2.0
    signup_fraction: float = 0.15        # sessions entering the funnel
    start_ts_ms: int = 1_700_000_000_000
    horizon_days: int = 2
    mean_gap_s: float = 18.0             # inter-event gap
    long_gap_prob: float = 0.02          # >30 min gap within one cookie
    seed: int = 0


@dataclass
class GeneratedLog:
    batch: EventBatch
    table: NameTable
    # ground truth for test assertions
    n_sessions_true: int = 0
    funnel_entries_true: int = 0


def build_name_table() -> NameTable:
    table = NameTable()
    for client in CLIENTS:
        for events in STATE_EVENTS.values():
            for suffix, _ in events:
                table.intern(f"{client}:{suffix}")
    return table


def generate(cfg: LogGenConfig) -> GeneratedLog:
    rng = np.random.default_rng(cfg.seed)
    table = build_name_table()
    trans = _transition_matrix()
    n_states = len(STATES)

    # Per-state event distributions as (state, client) -> code list + probs.
    state_event_ids = {}
    for s, events in STATE_EVENTS.items():
        for ci, client in enumerate(CLIENTS):
            ids = np.array([table.id_of(f"{client}:{suffix}")
                            for suffix, _ in events])
            w = np.array([w for _, w in events], np.float64)
            state_event_ids[(s, ci)] = (ids, w / w.sum())

    n_sessions = rng.poisson(cfg.sessions_per_user_mean,
                             cfg.n_users).clip(min=0)
    total_sessions = int(n_sessions.sum())
    sess_user = np.repeat(np.arange(cfg.n_users), n_sessions)
    # Stable per-user ids with realistic magnitudes.
    user_ids = (np.arange(cfg.n_users, dtype=np.int64) * 7_919 + 10**12)
    sess_client = rng.choice(len(CLIENTS), total_sessions,
                             p=[0.45, 0.25, 0.22, 0.08])
    # Cookie ids: per (user, device) cookie reused across that user's sessions.
    cookie = (user_ids[sess_user] * 17 + sess_client).astype(np.int64)

    # Markov chain over states, vectorized across sessions.
    start_state = np.where(rng.random(total_sessions) < cfg.signup_fraction,
                           _ST["signup_start"], _ST["home_browse"]).astype(np.int64)
    states = np.empty((total_sessions, cfg.max_steps), np.int64)
    states[:, 0] = start_state
    cum = trans.cumsum(axis=1)
    for t in range(1, cfg.max_steps):
        u = rng.random(total_sessions)
        states[:, t] = (cum[states[:, t - 1]] < u[:, None]).sum(axis=1)

    # Events per step (0 after the chain hits 'exit').
    alive = states != _ST["exit"]
    n_ev = rng.poisson(cfg.events_per_step_mean,
                       (total_sessions, cfg.max_steps)).clip(0, 6) * alive
    # Guarantee at least one event per session at step 0.
    n_ev[:, 0] = np.maximum(n_ev[:, 0], 1)

    # Session start times across the horizon.
    sess_start = (cfg.start_ts_ms
                  + rng.integers(0, cfg.horizon_days * 86_400_000,
                                 total_sessions))

    rows_name, rows_user, rows_sess, rows_ts, rows_ip, rows_init = \
        [], [], [], [], [], []
    ip_of_user = rng.integers(0, 2**31, cfg.n_users, dtype=np.int64)
    funnel_entries = 0
    for si in range(total_sessions):
        ci = int(sess_client[si])
        t_ms = int(sess_start[si])
        if states[si, 0] == _ST["signup_start"]:
            funnel_entries += 1
        for t in range(cfg.max_steps):
            k = int(n_ev[si, t])
            if k == 0:
                if not alive[si, t]:
                    break
                continue
            ids, p = state_event_ids[(STATES[states[si, t]], ci)]
            chosen = rng.choice(ids, size=k, p=p)
            for nid in chosen:
                gap = rng.exponential(cfg.mean_gap_s)
                if rng.random() < cfg.long_gap_prob:
                    gap += 1800 + rng.exponential(600)  # force session split
                t_ms += int(gap * 1000) + 1
                rows_name.append(int(nid))
                rows_user.append(int(user_ids[sess_user[si]]))
                rows_sess.append(int(cookie[si]))
                rows_ts.append(t_ms)
                rows_ip.append(int(ip_of_user[sess_user[si]]))
                rows_init.append(int(rng.random() < 0.9))  # mostly user-initiated

    n = len(rows_name)
    # The warehouse only guarantees *partial* time order (§2): shuffle within
    # coarse chunks to simulate aggregator interleaving.
    perm = np.arange(n)
    chunk = max(1, n // 64)
    for lo in range(0, n, chunk):
        seg = perm[lo:lo + chunk]
        rng.shuffle(seg)

    details = np.array(
        ['{"k":"v"}'] * n, dtype=object)
    batch = EventBatch(
        table=table,
        name_id=np.asarray(rows_name, np.int32)[perm],
        user_id=np.asarray(rows_user, np.int64)[perm],
        session_id=np.asarray(rows_sess, np.int64)[perm],
        ip=np.asarray(rows_ip, np.int64)[perm].astype(np.uint32),
        timestamp=np.asarray(rows_ts, np.int64)[perm],
        initiator=np.asarray(rows_init, np.int8)[perm],
        details=details,
    )
    return GeneratedLog(batch=batch, table=table,
                        n_sessions_true=total_sessions,
                        funnel_entries_true=funnel_entries)
