import pytest
from hypothesis import given, settings, strategies as st

from repro.core import namespace as ns

TOKEN = st.from_regex(r"[a-z][a-z0-9_]{0,8}", fullmatch=True)


def test_parse_canonical_roundtrip():
    name = "web:home:mentions:stream:avatar:profile_click"
    e = ns.parse(name)
    assert e.canonical() == name
    assert e.client == "web" and e.action == "profile_click"


def test_empty_middle_components_allowed():
    e = ns.parse("web:home::scroll_bar:scroll:impression")
    assert e.section == ""


@pytest.mark.parametrize("bad", [
    "Web:home:mentions:stream:avatar:click",      # uppercase
    "web:home:mentions:stream:avatar",            # 5 levels
    "web:home:mentions:stream:avatar:click:x",    # 7 levels
    "web:home:camel_Snake:stream:avatar:cLick",   # the dreaded camel_Snake
    ":home:mentions:stream:avatar:click",         # empty client
    "web:home:mentions:stream:avatar:",           # empty action
])
def test_invalid_names_rejected(bad):
    with pytest.raises(ns.InvalidEventName):
        ns.parse(bad)


@settings(deadline=None)
@given(st.lists(TOKEN, min_size=6, max_size=6))
def test_roundtrip_property(tokens):
    name = ":".join(tokens)
    assert ns.parse(name).canonical() == name


NAMES = [
    "web:home:mentions:stream:avatar:profile_click",
    "web:home:timeline:stream:tweet:impression",
    "iphone:home:mentions:stream:avatar:profile_click",
    "android:search:results:stream:tweet:click",
]


def test_suffix_glob():
    got = ns.match("web:home:mentions:*", NAMES)
    assert got == [NAMES[0]]


def test_prefix_glob_matches_all_clients():
    got = ns.match("*:profile_click", NAMES)
    assert set(got) == {NAMES[0], NAMES[2]}


def test_mid_level_single_wildcard():
    got = ns.match("web:home:*:stream:tweet:impression", NAMES)
    assert got == [NAMES[1]]


def test_rollup_schemas():
    e = ns.parse(NAMES[0])
    rollups = [e.rollup(s) for s in ns.ROLLUP_SCHEMAS]
    assert rollups[0] == NAMES[0]
    assert rollups[-1] == "web:*:*:*:*:profile_click"
    assert all(r.split(":")[0] == "web" and r.split(":")[-1] == "profile_click"
               for r in rollups)
