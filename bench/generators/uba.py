"""The benchmark's LUBM data generator, after LUBM's own (UBA).

LUBM (Guo, Pan, Heflin, J. Web Semantics 3(2), 2005) generates
university data over the univ-bench ontology with its generator UBA:
LUBM(N) is N universities of 15 to 25 departments each, and everything
else lives inside a department.  Faculty of four ranks work for it,
teach its courses and write publications; its undergraduate and graduate
students are members of it, take its courses and are advised by its
professors; some graduate students assist in teaching or research; its
research groups are parts of it.  Only the universities that faculty and
graduate students hold degrees from lie outside.

Every count and share is drawn from the ranges of the configuration's
`profile`, which holds UBA's published ranges; what UBA fixes that the
profile does not name is written here and listed under the
configuration's `assumed`.  Literals are as UBA writes them: a person's
name is its rank or kind and its number inside the department
("FullProfessor3"), its email address names the department and the
university, and every telephone number is "xxx-xxx-xxxx".

URIs are "Type/<zero-padded id>" with ids counted over the whole graph,
so a type's instances are one contiguous range of sorted labels; the
harness permutes each type's ids per `--seed`.

A configuration whose `generator` is "uba" is generated here
(`bench.traffic.make_data` finds this file by that name).
"""
from __future__ import annotations

import numpy as np

RANKS = ("FullProfessor", "AssociateProfessor", "AssistantProfessor",
         "Lecturer")
PROFESSORS = RANKS[:3]          # the ranks that advise students
TELEPHONE = "xxx-xxx-xxxx"


def generate(config: dict) -> tuple[list[tuple[str, str, str]], set[str],
                                    dict[str, int]]:
    """(triples, literal objects, instances per type) of LUBM(N), N the
    configuration's `universities`, drawn from its `data_seed`."""
    prof = config["profile"]
    rng = np.random.default_rng(int(config["data_seed"]))
    triples: list[tuple[str, str, str]] = []
    literals: set[str] = set()
    counts: dict[str, int] = {}
    add = triples.append

    def between(lo_hi) -> int:
        return int(rng.integers(lo_hi[0], lo_hi[1] + 1))

    def share(lo_hi) -> float:
        return float(rng.uniform(lo_hi[0], lo_hi[1]))

    def pick(items: list, k: int) -> list:
        k = min(k, len(items))
        return [items[i] for i in rng.choice(len(items), k, replace=False)]

    def new(kind: str) -> str:
        i = counts.get(kind, 0)
        counts[kind] = i + 1
        uri = f"{kind}/{i:08d}"
        add((uri, "type", f"Class/{kind}"))
        return uri

    def lit(s: str, p: str, value: str) -> None:
        add((s, p, value))
        literals.add(value)

    n_univ = int(config["universities"])
    univs = [new("University")
             for _ in range(max(n_univ, int(prof["degree_universities"])))]
    for k, u in enumerate(univs):
        lit(u, "name", f"University{k}")

    def degree_from() -> str:
        return univs[int(rng.integers(len(univs)))]

    for ui in range(n_univ):
        for di in range(between(prof["departments_per_university"])):
            dept = new("Department")
            lit(dept, "name", f"Department{di}")
            add((dept, "subOrganizationOf", univs[ui]))
            domain = f"Department{di}.University{ui}.edu"

            def person(kind: str, j: int) -> str:
                p = new(kind)
                lit(p, "name", f"{kind}{j}")
                lit(p, "emailAddress", f"{kind}{j}@{domain}")
                lit(p, "telephone", TELEPHONE)
                return p

            # faculty, their degrees and the courses they teach
            faculty = {r: [person(r, j)
                           for j in range(between(prof["faculty"][r]))]
                       for r in RANKS}
            courses, grad_courses = [], []
            for r in RANKS:
                for f in faculty[r]:
                    lit(f, "researchInterest", "Research"
                        f"{int(rng.integers(prof['research_interests']))}")
                    add((f, "worksFor", dept))
                    for p in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                              "doctoralDegreeFrom"):
                        add((f, p, degree_from()))
                    for kind, mine, key in (
                            ("Course", courses, "courses_per_faculty"),
                            ("GraduateCourse", grad_courses,
                             "graduate_courses_per_faculty")):
                        for _ in range(between(prof[key])):
                            c = new(kind)
                            lit(c, "name", f"{kind}{len(mine)}")
                            add((f, "teacherOf", c))
                            mine.append(c)
            add((pick(faculty["FullProfessor"], 1)[0], "headOf", dept))
            professors = [f for r in PROFESSORS for f in faculty[r]]
            n_fac = sum(len(v) for v in faculty.values())

            # students
            for j in range(round(n_fac * share(
                    prof["undergraduates_per_faculty"]))):
                s = person("UndergraduateStudent", j)
                add((s, "memberOf", dept))
                for c in pick(courses,
                              between(prof["courses_per_undergraduate"])):
                    add((s, "takesCourse", c))
                if rng.random() < prof["undergraduate_advisor_share"]:
                    add((s, "advisor", pick(professors, 1)[0]))
            grads = []
            for j in range(round(n_fac * share(
                    prof["graduates_per_faculty"]))):
                g = person("GraduateStudent", j)
                add((g, "memberOf", dept))
                add((g, "undergraduateDegreeFrom", degree_from()))
                for c in pick(grad_courses,
                              between(prof["graduate_courses_per_graduate"])):
                    add((g, "takesCourse", c))
                adv = pick(professors, 1)[0]
                add((g, "advisor", adv))
                grads.append((g, adv))
            students = [g for g, _ in grads]
            tas = pick(students, round(len(students) * share(
                prof["teaching_assistant_share"])))
            for g, c in zip(tas, pick(courses, len(tas))):
                add((g, "type", "Class/TeachingAssistant"))
                add((g, "teachingAssistantOf", c))
            for g in pick(students, round(len(students) * share(
                    prof["research_assistant_share"]))):
                add((g, "type", "Class/ResearchAssistant"))

            for _ in range(between(prof["research_groups_per_department"])):
                add((new("ResearchGroup"), "subOrganizationOf", dept))

            # publications: each faculty member's own, graduate students
            # co-authoring some of their advisor's
            written = {}
            for r in RANKS:
                for f in faculty[r]:
                    written[f] = []
                    for j in range(between(prof["publications"][r])):
                        pub = new("Publication")
                        lit(pub, "name", f"Publication{j}")
                        add((pub, "publicationAuthor", f))
                        written[f].append(pub)
            for g, adv in grads:
                for pub in pick(written[adv], between(
                        prof["publications_per_graduate"])):
                    add((pub, "publicationAuthor", g))
    return triples, literals, counts
