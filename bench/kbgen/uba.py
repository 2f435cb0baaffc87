"""A university in the proportions of LUBM's data generator (UBA).

The explicit facts of ``universities`` universities as the LUBM paper
(Guo, Pan, Heflin 2005, section 3.1) and UBA's profile describe them:
per department 7-10 full, 10-14 associate and 8-11 assistant professors
and 5-7 lecturers; 8-14 undergraduates and 3-4 graduate students per
faculty member; 1-2 courses and 1-2 graduate courses per faculty member;
10-20 research groups; a full professor as head; publications per
faculty rank; advisors, teaching and research assistants, degrees and
the literals (names, e-mail addresses, telephone numbers, research
interests) that UBA writes.  Classes are unary predicates, properties
binary ones, every value (a literal too) a constant id.

How many there are of everything is drawn from ``params["shape_seed"]``
alone, so every ``seed`` gives the same sizes; ``seed`` draws who is
related to whom: advisors, courses taken, co-authors, degrees, which
graduate students assist, research interests.  Ids are dense: the
universities a degree can name first (``University0`` is id 0), then the
departments, research groups, people, courses and publications, then
the literals.
"""

from __future__ import annotations

import numpy as np

RANKS = ("FullProfessor", "AssociateProfessor", "AssistantProfessor", "Lecturer")


def _span(rng, lo_hi, size=None):
    lo, hi = lo_hi
    return rng.integers(lo, hi + 1, size=size)


def _pick_each(rng, counts, pools):
    """For item ``i``, ``counts[i]`` distinct draws from ``pools[i]``;
    returns ``(owner index, drawn value)`` arrays."""
    owners, picks = [], []
    for i, (k, pool) in enumerate(zip(counts, pools)):
        if k:
            owners.append(np.full(k, i))
            picks.append(rng.choice(pool, size=k, replace=False))
    if not owners:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(owners), np.concatenate(picks)


class _Ids:
    def __init__(self):
        self.next = 0

    def take(self, n):
        out = np.arange(self.next, self.next + n, dtype=np.int64)
        self.next += n
        return out


def generate(params: dict, seed: int) -> dict[str, np.ndarray]:
    p = params
    shape = np.random.default_rng(int(p["shape_seed"]))
    rng = np.random.default_rng(seed)
    n_dept = int(p["departments"])

    # ---- sizes, from the shape seed alone ------------------------------
    rank_n = np.stack(
        [_span(shape, p[f"{r}_per_dept"], n_dept) for r in RANKS], axis=1
    )
    faculty_n = rank_n.sum(axis=1)
    ug_n = faculty_n * _span(shape, p["undergrads_per_faculty"], n_dept)
    grad_n = faculty_n * _span(shape, p["grads_per_faculty"], n_dept)
    group_n = _span(shape, p["groups_per_dept"], n_dept)
    # courses: each faculty member teaches 1-2 of each kind
    course_k = [_span(shape, p["courses_per_faculty"], f) for f in faculty_n]
    gcourse_k = [_span(shape, p["grad_courses_per_faculty"], f) for f in faculty_n]
    pubs_k = [
        np.concatenate([
            _span(shape, p[f"pubs_per_{r}"], rank_n[d, i])
            for i, r in enumerate(RANKS)
        ])
        for d in range(n_dept)
    ]
    ug_takes = [_span(shape, p["courses_per_undergrad"], n) for n in ug_n]
    grad_takes = [_span(shape, p["courses_per_grad"], n) for n in grad_n]
    grad_pubs = [_span(shape, p["pubs_per_grad"], n) for n in grad_n]
    ug_advised = [
        int(round(n / p["undergrads_per_advisor"])) for n in ug_n
    ]
    ta_n = [int(round(n / _span(shape, p["grads_per_ta"]))) for n in grad_n]
    ra_n = [int(round(n / _span(shape, p["grads_per_ra"]))) for n in grad_n]

    # ---- ids -----------------------------------------------------------
    ids = _Ids()
    univs = ids.take(int(p["universities"]))
    univ0 = univs[0]
    depts = ids.take(n_dept)
    groups = [ids.take(n) for n in group_n]
    faculty = [ids.take(n) for n in faculty_n]
    ugs = [ids.take(n) for n in ug_n]
    grads = [ids.take(n) for n in grad_n]
    courses = [ids.take(int(k.sum())) for k in course_k]
    gcourses = [ids.take(int(k.sum())) for k in gcourse_k]
    pubs = [ids.take(int(k.sum())) for k in pubs_k]
    # literals: a name is its class and its number in the department
    # ("FullProfessor3"), so names repeat across departments
    local = {
        "Department": n_dept,
        **{r: int(rank_n[:, i].max()) for i, r in enumerate(RANKS)},
        "UndergraduateStudent": int(ug_n.max()),
        "GraduateStudent": int(grad_n.max()),
        "Course": max(len(c) for c in courses),
        "GraduateCourse": max(len(c) for c in gcourses),
        "Publication": max(len(x) for x in pubs),
    }
    names = {k: ids.take(n) for k, n in local.items()}
    univ_name = ids.take(1)[0]
    people = [np.concatenate([f, u, g]) for f, u, g in zip(faculty, ugs, grads)]
    emails = ids.take(sum(len(x) for x in people))
    telephone = ids.take(1)[0]
    research = ids.take(int(p["research_topics"]))
    n_const = ids.next

    out: dict[str, list] = {}

    def add(pred, *cols):
        cols = [np.broadcast_to(np.asarray(c, np.int64), np.shape(cols[0])) for c in cols]
        out.setdefault(pred, []).append(np.stack(cols, axis=1))

    add("University", [univ0])
    add("name", [univ0], [univ_name])
    add("Department", depts)
    add("subOrganizationOf", depts, univ0)
    add("name", depts, names["Department"][: n_dept])
    email_at = 0
    for d in range(n_dept):
        dept = depts[d]
        add("ResearchGroup", groups[d])
        add("subOrganizationOf", groups[d], dept)

        fac = faculty[d]
        start = 0
        for i, r in enumerate(RANKS):
            these = fac[start: start + rank_n[d, i]]
            add(r, these)
            add("name", these, names[r][: len(these)])
            start += rank_n[d, i]
        profs = fac[: rank_n[d, :3].sum()]
        add("worksFor", fac, dept)
        add("headOf", fac[:1], dept)
        add("researchInterest", fac, research[rng.integers(0, len(research), len(fac))])
        for deg in ("undergraduateDegreeFrom", "mastersDegreeFrom", "doctoralDegreeFrom"):
            add(deg, fac, univs[rng.integers(0, len(univs), len(fac))])

        for kind, cs, ks in (
            ("Course", courses[d], course_k[d]),
            ("GraduateCourse", gcourses[d], gcourse_k[d]),
        ):
            add(kind, cs)
            add("name", cs, names[kind][: len(cs)])
            add("teacherOf", np.repeat(fac, ks), cs)

        ps = pubs[d]
        add("Publication", ps)
        add("name", ps, names["Publication"][: len(ps)])
        add("publicationAuthor", ps, np.repeat(fac, pubs_k[d]))

        ug, gr = ugs[d], grads[d]
        add("UndergraduateStudent", ug)
        add("name", ug, names["UndergraduateStudent"][: len(ug)])
        add("memberOf", ug, dept)
        o, c = _pick_each(rng, ug_takes[d], [courses[d]] * len(ug))
        add("takesCourse", ug[o], c)
        advised = rng.choice(ug, size=ug_advised[d], replace=False)
        add("advisor", advised, profs[rng.integers(0, len(profs), len(advised))])

        add("GraduateStudent", gr)
        add("name", gr, names["GraduateStudent"][: len(gr)])
        add("memberOf", gr, dept)
        add("undergraduateDegreeFrom", gr, univs[rng.integers(0, len(univs), len(gr))])
        o, c = _pick_each(rng, grad_takes[d], [gcourses[d]] * len(gr))
        add("takesCourse", gr[o], c)
        add("advisor", gr, profs[rng.integers(0, len(profs), len(gr))])
        o, c = _pick_each(rng, grad_pubs[d], [ps] * len(gr))
        add("publicationAuthor", c, gr[o])
        assist = rng.permutation(gr)
        tas, ras = assist[: ta_n[d]], assist[ta_n[d]: ta_n[d] + ra_n[d]]
        add("TeachingAssistant", tas)
        add("teachingAssistantOf", tas, rng.choice(
            courses[d], size=len(tas), replace=len(tas) > len(courses[d])))
        add("ResearchAssistant", ras)

        ppl = people[d]
        add("emailAddress", ppl, emails[email_at: email_at + len(ppl)])
        add("telephone", ppl, telephone)
        email_at += len(ppl)

    assert n_const <= int(p["max_constants"]), (n_const, p["max_constants"])
    return {k: np.unique(np.concatenate(v), axis=0) for k, v in out.items()}
