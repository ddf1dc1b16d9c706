"""Plain reference resolver: deppy's resolution semantics, written out.

It reads one problem in the service's wire form (``{"variables": [...]}``)
and returns the answer in the wire form the service renders: the
installed set, or the ``NotSatisfiable`` core.  It imports nothing of the
program under test; it is the yardstick that decides ``correct``.

The semantics, as deppy defines them (its README's examples and
pkg/sat/solve.go, search.go):

1. Every applied constraint is enforced.  Mandatory variables are the
   search's anchors.
2. Unit propagation ("Test") over the clauses and AtMost rows decides
   what follows from a set of assumptions: a conflict, a total
   assignment, or neither.
3. The guess search walks a deque of choices: the anchors, then each
   guessed variable's Dependency candidate lists, candidates in
   preference order, depth first, retrying the next candidate of a choice
   whose guess fails.  When the deque is empty and propagation has not
   decided, a complete search finishes the model: the lowest-numbered open
   variable is tried false first, with chronological backtracking, which
   yields the least model in that order.
4. On SAT, the variables that are true but were never guessed ("extras")
   are minimized in number: guesses stay true, model-false variables stay
   false, and the least model with the fewest extras is installed.
5. On UNSAT, the core is found by deletion: constraints are dropped one
   at a time in their order whenever the rest stays unsatisfiable.

:func:`control` is the same resolver with the two steps a faster
implementation is tempted to skip: it answers SAT problems with the least
model of the constraints (no preference-ordered search, no
minimization) and UNSAT problems with every applied constraint (no core
minimization).
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

SAT, UNSAT, UNKNOWN = 1, -1, 0


class Problem:
    """One problem lowered to clauses (signed 1-based literals) and
    AtMost rows, each tagged with its applied constraint."""

    def __init__(self, doc: dict):
        variables = doc["variables"]
        self.ids = [v["id"] for v in variables]
        index = {ident: i for i, ident in enumerate(self.ids)}
        if len(index) != len(self.ids):
            raise ValueError("duplicate identifier")
        self.n = len(self.ids)
        self.texts: List[str] = []
        self.clauses: List[Tuple[Tuple[int, ...], int]] = []
        self.cards: List[Tuple[Tuple[int, ...], int, int]] = []
        self.anchors: List[int] = []
        self.choices: List[List[int]] = []
        self.var_choices: List[List[int]] = [[] for _ in range(self.n)]
        for i, v in enumerate(variables):
            s = v["id"]
            for con in v.get("constraints", []):
                j = len(self.texts)
                kind = con["type"]
                if kind == "mandatory":
                    self.texts.append(f"{s} is mandatory")
                    self.clauses.append(((i + 1,), j))
                    if i not in self.anchors:
                        self.anchors.append(i)
                elif kind == "prohibited":
                    self.texts.append(f"{s} is prohibited")
                    self.clauses.append(((-(i + 1),), j))
                elif kind == "dependency":
                    ids = con["ids"]
                    self.texts.append(
                        f"{s} requires at least one of {', '.join(ids)}" if ids
                        else f"{s} has a dependency without any candidates "
                             "to satisfy it")
                    cands: List[int] = []
                    for t in (index[x] for x in ids):
                        if t not in cands:
                            cands.append(t)
                    self.clauses.append(
                        (tuple([-(i + 1)] + [t + 1 for t in cands]), j))
                    if ids:
                        self.var_choices[i].append(len(self.choices))
                        self.choices.append(cands)
                elif kind == "conflict":
                    self.texts.append(f"{s} conflicts with {con['id']}")
                    t = index[con["id"]]
                    lits = (-(i + 1),) if t == i else (-(i + 1), -(t + 1))
                    self.clauses.append((lits, j))
                elif kind == "atMost":
                    ids = con["ids"]
                    self.texts.append(
                        f"{s} permits at most {con['n']} of {', '.join(ids)}")
                    members: List[int] = []
                    for m in (index[x] for x in ids):
                        if m not in members:
                            members.append(m)
                    self.cards.append((tuple(members), con["n"], j))
                else:
                    raise ValueError(f"unknown constraint type {kind!r}")
        self.n_cons = len(self.texts)


class _Rules:
    """The clauses and AtMost rows of a set of enabled constraints, with
    per-variable occurrence lists, and unit propagation over them."""

    def __init__(self, p: Problem, enabled: Optional[Sequence[bool]] = None,
                 extra_card: Optional[Tuple[Tuple[int, ...], int]] = None):
        self.n = p.n
        self.clauses = [lits for lits, j in p.clauses
                        if enabled is None or enabled[j]]
        self.cards = [(m, k) for m, k, j in p.cards
                      if enabled is None or enabled[j]]
        if extra_card is not None:
            self.cards.append(extra_card)
        self.occ: List[List[int]] = [[] for _ in range(p.n)]
        for c, lits in enumerate(self.clauses):
            for lit in lits:
                self.occ[abs(lit) - 1].append(c)
        self.card_occ: List[List[int]] = [[] for _ in range(p.n)]
        for r, (members, _) in enumerate(self.cards):
            for m in members:
                self.card_occ[m].append(r)

    def _clause(self, val: List[int], c: int, todo: List[int]) -> bool:
        """Check one clause; assign its unit literal.  False on conflict."""
        last = 0
        open_ = 0
        for lit in self.clauses[c]:
            x = val[abs(lit) - 1]
            if x == 0:
                open_ += 1
                last = lit
            elif (x > 0) == (lit > 0):
                return True
        if open_ == 0:
            return False
        if open_ == 1:
            v = abs(last) - 1
            val[v] = 1 if last > 0 else -1
            todo.append(v)
        return True

    def _card(self, val: List[int], r: int, todo: List[int]) -> bool:
        """Check one AtMost row; at its bound, the rest go false."""
        members, k = self.cards[r]
        trues = sum(1 for m in members if val[m] > 0)
        if trues > k:
            return False
        if trues == k:
            for m in members:
                if val[m] == 0:
                    val[m] = -1
                    todo.append(m)
        return True

    def propagate(self, val: List[int], todo: Optional[List[int]] = None) -> bool:
        """Unit propagation to fixpoint, in place.  With ``todo`` None
        every rule is checked first.  False on conflict."""
        if todo is None:
            todo = []
            for c in range(len(self.clauses)):
                if not self._clause(val, c, todo):
                    return False
            for r in range(len(self.cards)):
                if not self._card(val, r, todo):
                    return False
        while todo:
            v = todo.pop()
            for c in self.occ[v]:
                if not self._clause(val, c, todo):
                    return False
            if val[v] > 0:
                for r in self.card_occ[v]:
                    if not self._card(val, r, todo):
                        return False
        return True

    def least_model(self, val: List[int]) -> Optional[List[int]]:
        """The least model extending ``val`` (lowest-numbered open
        variable false first, chronological backtracking), or None."""
        val = list(val)
        if not self.propagate(val):
            return None
        stack = []  # (variable, value list before its decision)
        while True:
            v = next((i for i in range(self.n) if val[i] == 0), -1)
            if v < 0:
                return val
            trial = list(val)
            trial[v] = -1
            stack.append((v, val))
            if self.propagate(trial, [v]):
                val = trial
                continue
            while True:
                if not stack:
                    return None
                v, before = stack.pop()
                if before is None:
                    continue  # this decision already took its true branch
                trial = list(before)
                trial[v] = 1
                stack.append((v, None))
                if self.propagate(trial, [v]):
                    val = trial
                    break


def _assume(n: int, true: Sequence[int] = (), false: Sequence[int] = ()) -> List[int]:
    val = [0] * n
    for v in true:
        val[v] = 1
    for v in false:
        val[v] = -1
    return val


def _test(rules: _Rules, p: Problem, guessed: Sequence[int]) -> Tuple[int, List[int]]:
    val = _assume(p.n, list(p.anchors) + list(guessed))
    if not rules.propagate(val):
        return UNSAT, val
    return (SAT if all(val) else UNKNOWN), val


def _search(rules: _Rules, p: Problem) -> Tuple[int, List[int], Optional[List[int]]]:
    """The preference-ordered guess search.  Returns (outcome, guessed
    variables, model)."""
    dq = deque(("anchor", r, 0) for r in range(len(p.anchors)))
    guesses: List[list] = []  # [choice, index, var, children]
    result, model = UNKNOWN, None

    def cands_of(kind, row):
        return [p.anchors[row]] if kind == "anchor" else p.choices[row]

    def assumed() -> List[int]:
        return [g[3] for g in guesses if g[3] >= 0]

    while True:
        if not dq and result == UNKNOWN:
            model = rules.least_model(_assume(p.n, list(p.anchors) + assumed()))
            result = SAT if model is not None else UNSAT
        if result == UNSAT:
            if not guesses:
                break
            kind, row, idx, var, children = guesses.pop()
            for _ in range(children):
                dq.pop()
            dq.appendleft((kind, row, idx + (1 if var >= 0 else 0)))
            if var >= 0:
                result, val = _test(rules, p, assumed())
                if result == SAT:
                    model = val
            continue
        if not dq:
            break
        kind, row, idx = dq.popleft()
        cands = cands_of(kind, row)
        var = cands[idx] if idx < len(cands) else -1
        taken = set(assumed())
        if any(c in taken for c in cands):
            var = -1
        g = [kind, row, idx, var, 0]
        guesses.append(g)
        if var < 0:
            continue
        for ch in p.var_choices[var]:
            g[4] += 1
            dq.append(("dep", ch, 0))
        result, val = _test(rules, p, assumed())
        if result == SAT:
            model = val
    return result, assumed(), model


def _minimize(p: Problem, model: List[int], guessed: set) -> List[int]:
    """The least model with the fewest extras; returns installed indices."""
    extras = [i for i in range(p.n) if model[i] > 0 and i not in guessed]
    excluded = [i for i in range(p.n) if model[i] <= 0 and i not in guessed]
    for w in range(len(extras) + 1):
        rules = _Rules(p, extra_card=(tuple(extras), w))
        m = rules.least_model(_assume(p.n, list(p.anchors) + sorted(guessed),
                                      excluded))
        if m is not None:
            return [i for i in range(p.n) if m[i] > 0]
    raise RuntimeError("minimization failed")


def _unsatisfiable(p: Problem, enabled: List[bool]) -> bool:
    return _Rules(p, enabled).least_model([0] * p.n) is None


def _core(p: Problem) -> List[int]:
    """Deletion-minimal core, dropping constraints in their order."""
    enabled = [True] * p.n_cons
    for j in range(p.n_cons):
        trial = list(enabled)
        trial[j] = False
        if _unsatisfiable(p, trial):
            enabled = trial
    return [j for j in range(p.n_cons) if enabled[j]]


def _sat_doc(p: Problem, installed: Sequence[int]) -> dict:
    on = set(installed)
    return {"status": "sat",
            "selected": sorted(p.ids[i] for i in on),
            "solution": {ident: i in on for i, ident in enumerate(p.ids)}}


def _unsat_doc(p: Problem, core: Sequence[int]) -> dict:
    return {"status": "unsat", "conflicts": [p.texts[j] for j in core]}


def resolve(doc: dict) -> dict:
    """The answer to one wire-form problem, in the service's wire form."""
    p = Problem(doc)
    rules = _Rules(p)
    outcome, val = _test(rules, p, ())
    if outcome == SAT:
        return _sat_doc(p, _minimize(p, val, set(p.anchors)))
    if outcome == UNKNOWN:
        outcome, guessed, model = _search(rules, p)
        if outcome == SAT:
            return _sat_doc(p, _minimize(p, model, set(guessed)))
    return _unsat_doc(p, _core(p))


def control(doc: dict) -> dict:
    """:func:`resolve` without the preference-ordered search, the
    minimization and the core minimization (see the module docstring)."""
    p = Problem(doc)
    m = _Rules(p).least_model(_assume(p.n, p.anchors))
    if m is None:
        return _unsat_doc(p, range(p.n_cons))
    return _sat_doc(p, [i for i in range(p.n) if m[i] > 0])


def canonical(answer: dict) -> str:
    """One answer as canonical JSON: the form two answers are compared in."""
    import json

    return json.dumps(answer, sort_keys=True)


def compare(got: Sequence[Optional[dict]],
            problems: Sequence[dict]) -> Dict[str, object]:
    """Compare received answers with the reference's, one by one.  A None
    answer is missing.  Returns counts and the first difference."""
    mismatched = missing = 0
    first = ""
    for answer, doc in zip(got, problems):
        if answer is None:
            missing += 1
            continue
        want = canonical(resolve(doc))
        have = canonical(answer)
        if have != want:
            mismatched += 1
            if not first:
                first = f"got {have[:300]} want {want[:300]}"
    return {"compared": len(got) - missing, "mismatched": mismatched,
            "missing": missing, "first_difference": first}
