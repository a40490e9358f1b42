"""The benchmark's own copy of the LUBM data generator.

It emits the same string triples, in the same order, as the program's
``repro.kg.generator`` for the same generator seed, but as plain
``(s, p, o)`` string tuples: the program builds its store from them through
``TripleStore.from_string_triples`` and the plain reference encodes them
itself. Kept here so that no later change to the program can change the
data a cell is measured on.

LUBM: Guo, Pan & Heflin, "LUBM: A benchmark for OWL knowledge base systems",
J. Web Semantics 3(2-3), 2005.
"""
from __future__ import annotations

import numpy as np


def lubm(n_universities: int = 1, *, scale: float = 1.0,
         seed: int = 0) -> list[tuple[str, str, str]]:
    """LUBM-shaped university data; scale 1.0 is one university per unit."""
    rng = np.random.default_rng(seed)
    t: list[tuple[str, str, str]] = []
    add = t.append

    def k(lo: int, hi: int) -> int:
        v = int(round(rng.integers(lo, hi + 1) * scale))
        return max(1, v)

    unis = [f"ub:University{u}" for u in range(max(2, n_universities + 2))]
    for uname in unis:
        add((uname, "rdf:type", "ub:University"))

    for u in range(n_universities):
        uni = unis[u]
        n_dept = k(12, 18)
        for d in range(n_dept):
            dept = f"ub:U{u}_Dept{d}"
            add((dept, "rdf:type", "ub:Department"))
            add((dept, "ub:subOrganizationOf", uni))

            n_rg = k(10, 15)
            for g in range(n_rg):
                rgrp = f"{dept}_Group{g}"
                add((rgrp, "rdf:type", "ub:ResearchGroup"))
                add((rgrp, "ub:subOrganizationOf", dept))

            n_course = k(25, 35)
            n_gcourse = k(15, 25)
            courses = [f"{dept}_Course{i}" for i in range(n_course)]
            gcourses = [f"{dept}_GraduateCourse{i}" for i in range(n_gcourse)]
            for cn in courses:
                add((cn, "rdf:type", "ub:Course"))
            for cn in gcourses:
                add((cn, "rdf:type", "ub:GraduateCourse"))
                add((cn, "rdf:type", "ub:Course"))

            fac_specs = [("FullProfessor", k(7, 10)),
                         ("AssociateProfessor", k(10, 14)),
                         ("AssistantProfessor", k(8, 11)),
                         ("Lecturer", k(5, 7))]
            faculty: list[str] = []
            professors: list[str] = []
            for cls, n in fac_specs:
                for i in range(n):
                    f = f"{dept}_{cls}{i}"
                    faculty.append(f)
                    add((f, "rdf:type", f"ub:{cls}"))
                    if cls != "Lecturer":
                        professors.append(f)
                        add((f, "rdf:type", "ub:Professor"))
                    add((f, "rdf:type", "ub:Faculty"))
                    add((f, "rdf:type", "ub:Person"))
                    add((f, "ub:worksFor", dept))
                    add((f, "ub:memberOf", dept))
                    add((f, "ub:undergraduateDegreeFrom",
                         unis[rng.integers(len(unis))]))
                    add((f, "ub:mastersDegreeFrom",
                         unis[rng.integers(len(unis))]))
                    add((f, "ub:doctoralDegreeFrom",
                         unis[rng.integers(len(unis))]))
                    add((f, "ub:name", f"lit:name_{f}"))
                    add((f, "ub:emailAddress", f"lit:email_{f}"))
                    add((f, "ub:telephone", f"lit:tel_{f}"))
                    add((f, "ub:researchInterest",
                         f"lit:research{rng.integers(30)}"))

            head = f"{dept}_FullProfessor0"
            add((head, "ub:headOf", dept))
            add((head, "rdf:type", "ub:Chair"))

            for cn in courses:
                add((faculty[rng.integers(len(faculty))], "ub:teacherOf", cn))
            for cn in gcourses:
                add((professors[rng.integers(len(professors))],
                     "ub:teacherOf", cn))

            for f in faculty:
                for pub_i in range(int(rng.integers(3, 8))):
                    pub = f"{f}_Pub{pub_i}"
                    add((pub, "rdf:type", "ub:Publication"))
                    add((pub, "ub:publicationAuthor", f))

            n_under = int(len(faculty) * rng.uniform(8, 12))
            n_grad = int(len(faculty) * rng.uniform(3, 4))
            for i in range(n_under):
                s = f"{dept}_UndergraduateStudent{i}"
                add((s, "rdf:type", "ub:UndergraduateStudent"))
                add((s, "rdf:type", "ub:Student"))
                add((s, "rdf:type", "ub:Person"))
                add((s, "ub:memberOf", dept))
                add((s, "ub:name", f"lit:name_{s}"))
                add((s, "ub:emailAddress", f"lit:email_{s}"))
                add((s, "ub:telephone", f"lit:tel_{s}"))
                for cn in rng.choice(n_course,
                                     size=min(n_course,
                                              int(rng.integers(2, 5))),
                                     replace=False):
                    add((s, "ub:takesCourse", courses[cn]))
                if rng.uniform() < 0.2:
                    add((s, "ub:advisor",
                         professors[rng.integers(len(professors))]))
            for i in range(n_grad):
                s = f"{dept}_GraduateStudent{i}"
                add((s, "rdf:type", "ub:GraduateStudent"))
                add((s, "rdf:type", "ub:Student"))
                add((s, "rdf:type", "ub:Person"))
                add((s, "ub:memberOf", dept))
                add((s, "ub:name", f"lit:name_{s}"))
                add((s, "ub:emailAddress", f"lit:email_{s}"))
                add((s, "ub:telephone", f"lit:tel_{s}"))
                add((s, "ub:undergraduateDegreeFrom",
                     unis[rng.integers(len(unis))]))
                add((s, "ub:advisor",
                     professors[rng.integers(len(professors))]))
                for cn in rng.choice(n_gcourse,
                                     size=min(n_gcourse,
                                              int(rng.integers(1, 4))),
                                     replace=False):
                    add((s, "ub:takesCourse", gcourses[cn]))
                if rng.uniform() < 0.2:
                    add((s, "ub:teachingAssistantOf",
                         courses[rng.integers(n_course)]))
    return t


GENERATORS = {
    "lubm": lambda g: lubm(int(g["universities"]), scale=float(g["scale"]),
                           seed=int(g["seed"])),
}


def generate(spec: dict) -> list[tuple[str, str, str]]:
    """String triples for a configuration's ``generator`` block."""
    return GENERATORS[spec["name"]](spec)
