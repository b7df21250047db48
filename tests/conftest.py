import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

# Tests run on the single real CPU device — the 512-device override is
# strictly dryrun.py's (subprocess tests set their own XLA_FLAGS).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session", params=[dict(n_users=250, seed=123)],
                ids=lambda p: f"loggen-u{p['n_users']}-s{p['seed']}")
def loggen_corpus(request):
    """One shared loggen day (events + dictionary codes + funnel stages).

    Session-scoped and parametrized so the batch (test_distpipe) and
    streaming (test_streampipe) equivalence tests consume byte-identical
    inputs — the paper's signup funnel among them — without regenerating
    the corpus per test.
    """
    from repro.core import EventDictionary
    from repro.data import LogGenConfig, generate
    from repro.data.loggen import SIGNUP_FUNNEL
    p = request.param
    log = generate(LogGenConfig(n_users=p["n_users"], seed=p["seed"],
                                signup_fraction=0.25))
    b = log.batch
    d = EventDictionary.build(b.table, b.name_id)
    codes = np.asarray(d.encode_ids(b.name_id), np.int32)
    return SimpleNamespace(
        user_id=b.user_id, session_id=b.session_id, timestamp=b.timestamp,
        code=codes, ip=b.ip.astype(np.int64),
        alphabet_size=d.alphabet_size, dictionary=d,
        stages=[d.codes_matching(pat) for pat in SIGNUP_FUNNEL],
        n_events=len(b))
