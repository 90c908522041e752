"""The benchmark's LUBM generator keeps UBA's profile: every count inside
its published range, and everything but degrees inside one department;
its data at the cell's size and at a small one is pinned by hash; and
it keeps the instance-label convention that `check_generated` asserts."""
import hashlib
import json
from collections import Counter, defaultdict

import pytest

from bench.generators.uba import RANKS
from bench.generators.uba import generate as uba
from bench.traffic import BENCH, check_generated


def _config(data_seed):
    config = json.loads((BENCH / "configs" / "lubm.json").read_text())
    config.update(universities=2, data_seed=data_seed)
    config["profile"]["departments_per_university"] = [2, 3]
    return config


def _index(triples):
    out = defaultdict(list)          # (subject, predicate) -> objects
    types = defaultdict(set)
    for s, p, o in triples:
        out[s, p].append(o)
        if p == "type":
            types[o.removeprefix("Class/")].add(s)
    return out, types


def _inside(lo_hi, x, slack=0):
    return lo_hi[0] - slack <= x <= lo_hi[1] + slack


@pytest.mark.parametrize("data_seed", [0, 1, 2])
def test_bench_uba_counts_in_profile(data_seed):
    config = _config(data_seed)
    prof = config["profile"]
    triples, literals, counts = uba(config)
    out, types = _index(triples)
    depts = sorted(types["Department"])
    assert 2 * 2 <= len(depts) <= 2 * 3
    assert counts["University"] == prof["degree_universities"]
    for dept in depts:
        works = Counter()
        for r in RANKS:
            members = [f for f in types[r] if out[f, "worksFor"] == [dept]]
            works[r] = len(members)
            assert _inside(prof["faculty"][r], len(members)), r
            for f in members:
                assert _inside(prof["publications"][r],
                               sum(out[p, "publicationAuthor"][0] == f
                                   for p in types["Publication"])), r
        n_fac = sum(works.values())
        students = {k: [s for s in types[k] if out[s, "memberOf"] == [dept]]
                    for k in ("UndergraduateStudent", "GraduateStudent")}
        # head counts are rounded from a ratio to the faculty
        assert _inside([n_fac * x for x in prof["undergraduates_per_faculty"]],
                       len(students["UndergraduateStudent"]), slack=0.5)
        assert _inside([n_fac * x for x in prof["graduates_per_faculty"]],
                       len(students["GraduateStudent"]), slack=0.5)
        for s in students["UndergraduateStudent"]:
            assert _inside(prof["courses_per_undergraduate"],
                           len(out[s, "takesCourse"]))
        for s in students["GraduateStudent"]:
            assert _inside(prof["graduate_courses_per_graduate"],
                           len(out[s, "takesCourse"]))
            assert len(out[s, "advisor"]) == 1
        assert _inside(prof["research_groups_per_department"],
                       sum(out[g, "subOrganizationOf"] == [dept]
                           for g in types["ResearchGroup"]))
    assert all(out[p, "telephone"] == ["xxx-xxx-xxxx"]
               for k in ("UndergraduateStudent", "GraduateStudent")
               for p in types[k])
    assert "xxx-xxx-xxxx" in literals


def test_bench_uba_is_department_local():
    triples, _, _ = uba(_config(3))
    out, types = _index(triples)
    dept_of = {}
    for s, p, o in triples:
        if p in ("worksFor", "memberOf"):
            dept_of[s] = o
    for f in (f for r in RANKS for f in types[r]):
        for c in out[f, "teacherOf"]:
            dept_of[c] = dept_of[f]
    for s, p, o in triples:
        if p in ("takesCourse", "advisor", "teachingAssistantOf"):
            assert dept_of[s] == dept_of[o], (s, p, o)
        if p == "publicationAuthor":
            first = out[s, "publicationAuthor"][0]
            assert dept_of[o] == dept_of[first], (s, p, o)
    heads = [(f, d) for f in types["FullProfessor"]
             for d in out[f, "headOf"]]
    assert sorted(d for _, d in heads) == sorted(types["Department"])
    assert all(dept_of[f] == d for f, d in heads)


def test_bench_uba_same_seed_same_data():
    assert uba(_config(5)) == uba(_config(5))
    assert uba(_config(5))[0] != uba(_config(6))[0]


def _lubm(small):
    config = json.loads((BENCH / "configs" / "lubm.json").read_text())
    if small:
        config["universities"] = 1
        config["profile"]["departments_per_university"] = [2, 2]
    return config


@pytest.mark.parametrize("small,n_triples,digest", [
    (True, 15_578, "e64f1be0cd689ed1"),
    (False, 522_428, "f7f580a3124b649b"),
], ids=["small", "lubm.json"])
def test_bench_uba_golden(small, n_triples, digest):
    """The data the cell serves, byte for byte: sha256 over the sorted
    triples, tab-separated, one a line."""
    triples, _, _ = uba(_lubm(small))
    assert len(triples) == n_triples
    text = "\n".join("\t".join(t) for t in sorted(triples))
    assert hashlib.sha256(text.encode()).hexdigest().startswith(digest)


def test_bench_check_generated_passes_uba():
    check_generated(*uba(_lubm(True)))


def _paper_graph():
    triples = [("Paper/00000000", "cites", "Paper/00000001"),
               ("Paper/00000001", "title", "Title1")]
    return triples, {"Title1"}, {"Paper": 2}


def _break(how):
    triples, literals, counts = _paper_graph()
    if how == "unpadded":
        triples[0] = ("Paper/7", "cites", "Paper/00000001")
    elif how == "count_disagrees":
        counts["Paper"] = 3
    elif how == "uncounted_type":
        triples.append(("Author/00000000", "wrote", "Paper/00000000"))
    elif how == "not_strings":
        triples.append(("Paper/00000000", "year", 2009))
    elif how == "literal_not_an_object":
        literals.add("Title2")
    return triples, literals, counts


def test_bench_check_generated_accepts_the_convention():
    check_generated(*_break("none"))


@pytest.mark.parametrize("how,says", [
    ("unpadded", "instance counts say 2, the labels hold 1 ids"),
    ("count_disagrees", "instance counts say 3, the labels hold 2 ids"),
    ("uncounted_type", "types not in the instance counts: ..Author.."),
    ("not_strings", "is not three strings"),
    ("literal_not_an_object", "literal objects are no triple's object"),
])
def test_bench_check_generated_refuses(how, says):
    with pytest.raises(ValueError, match=says):
        check_generated(*_break(how))
