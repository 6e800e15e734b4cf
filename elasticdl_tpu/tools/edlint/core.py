"""edlint engine: file walker, per-file AST context, ratchet, report.

A rule is an object with ``id``, ``name``, ``doc`` and a
``check(ctx) -> [Finding]`` method over a :class:`FileContext` — one
parsed module plus the binding tables most concurrency rules need
(which names/attributes in this file hold ``queue.Queue``\\ s, locks,
conditions, threads). Rules live in ``rules.py``; the allowlist
ratchets (per rule, per file, max count + reason) live in
``ratchet.py``.

The ratchet discipline is the same one greps_guard established: an
allowlist entry is a per-file MAXIMUM occurrence count. New code that
trips a rule must adopt the safe pattern or consciously extend the
ratchet with a reason in the same review; entries only ever shrink
(``--stale`` reports entries whose budget exceeds current use).
"""

import argparse
import ast
import json
import os
import sys
from collections import namedtuple

Finding = namedtuple("Finding", "rule path lineno message text")

# binding "kinds": ("name", "q") for a local/module name, ("attr", "_q")
# for an attribute (self._q / service._q — keyed by the attribute name
# alone, which is how humans keep these unambiguous within one file)

QUEUE_UNBOUNDED = "unbounded"
QUEUE_BOUNDED = "bounded"


def binding_of(node):
    """Binding key for an expression used as receiver/target, or None."""
    if isinstance(node, ast.Name):
        return ("name", node.id)
    if isinstance(node, ast.Attribute):
        return ("attr", node.attr)
    return None


def dotted(node):
    """Dotted name of an expression ("jax.devices", "self._q.put"), or
    "" when any link is not a plain Name/Attribute."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def call_kwarg(call, name):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _queue_boundedness(call):
    """Boundedness of a ``queue.Queue(...)``-style constructor call."""
    size = call_kwarg(call, "maxsize")
    if size is None and call.args:
        size = call.args[0]
    if size is None:
        return QUEUE_UNBOUNDED
    if isinstance(size, ast.Constant) and not size.value:
        return QUEUE_UNBOUNDED  # maxsize=0/None: never blocks on put
    return QUEUE_BOUNDED


_QUEUE_CTORS = ("Queue", "LifoQueue", "PriorityQueue", "SimpleQueue")
_LOCK_CTORS = ("Lock", "RLock")


class FileContext:
    """One parsed source file plus the binding tables rules share."""

    def __init__(self, path, source, tree=None):
        self.path = path  # repo-relative, posix
        self.source = source
        self.lines = source.splitlines()
        # ``tree`` lets the project layer's mtime-keyed AST cache skip
        # the re-parse (elasticdl_tpu/tools/edlint/project.py)
        self.tree = tree if tree is not None else ast.parse(
            source, filename=path
        )
        # whole-program context; scan() attaches the Project so rules
        # R5/R8/R9 can resolve across files (None for standalone use)
        self.project = None
        self.parent = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
        # binding -> QUEUE_BOUNDED | QUEUE_UNBOUNDED
        self.queue_bindings = {}
        # bindings assigned threading.Lock()/RLock() (not Conditions)
        self.lock_bindings = set()
        self.condition_bindings = set()
        self._collect_bindings()

    def __getstate__(self):
        # the AST cache pickles whole FileContexts; a live Project
        # reference would drag the entire cross-file index (and every
        # other file) into each entry
        state = dict(self.__dict__)
        state["project"] = None
        return state

    def line(self, node):
        return self.line_at(node.lineno)

    def line_at(self, lineno):
        try:
            return self.lines[lineno - 1].strip()
        except IndexError:
            return ""

    def _collect_bindings(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = node.value
            if not isinstance(value, ast.Call):
                continue
            tail = dotted(value.func).rsplit(".", 1)[-1]
            targets = (
                node.targets
                if isinstance(node, ast.Assign)
                else [node.target]
            )
            for target in targets:
                b = binding_of(target)
                if b is None:
                    continue
                if tail in _QUEUE_CTORS:
                    if tail == "SimpleQueue":
                        self.queue_bindings[b] = QUEUE_UNBOUNDED
                    else:
                        self.queue_bindings[b] = _queue_boundedness(value)
                elif tail in _LOCK_CTORS:
                    self.lock_bindings.add(b)
                elif tail == "Condition":
                    self.condition_bindings.add(b)

    def enclosing(self, node, kinds):
        """Nearest ancestor of ``node`` matching ``kinds`` (or None)."""
        cur = self.parent.get(node)
        while cur is not None and not isinstance(cur, kinds):
            cur = self.parent.get(cur)
        return cur

    def walk_shallow(self, node, stop=()):
        """Walk ``node``'s subtree without descending into ``stop``
        node types (used to keep "lexically inside" honest — a nested
        ``def``'s body does not run under the enclosing lock)."""
        stack = list(ast.iter_child_nodes(node))
        while stack:
            cur = stack.pop()
            yield cur
            if not isinstance(cur, stop):
                stack.extend(ast.iter_child_nodes(cur))


def iter_source_files(root):
    """Scanned scope: the package tree, the model zoo, scripts, and the
    top-level entry points. Tests are deliberately out of scope — they
    hold known-bad fixtures for these very rules."""
    path = os.path.join(root, "__graft_entry__.py")
    if os.path.exists(path):
        yield path
    for pkg in ("elasticdl_tpu", "model_zoo", "scripts"):
        top = os.path.join(root, pkg)
        for dirpath, dirnames, names in os.walk(top):
            dirnames[:] = sorted(
                d for d in dirnames if d != "__pycache__"
            )
            for name in sorted(names):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def scan_project(root, rule_ids=None, use_cache=True, only_paths=None):
    """All raw findings over ``root`` (before the ratchet), in
    (path, lineno) order, plus files that failed to parse, plus the
    Project the rules ran against.

    Every scan is whole-program: the modules parse once (through the
    mtime-keyed AST cache unless ``use_cache=False``), a Project is
    built over all of them, and each rule sees per-file contexts that
    carry the cross-file call graph (``ctx.project``). ``only_paths``
    (repo-relative) is the incremental mode: rules run ONLY on the
    named files, but resolution — the call graph, thread roots, R8
    locksets, the R11 lock graph — still spans the whole tree, so a
    cross-file finding surfaced in a named file stays correct. When
    nothing changed since the last cached run, the whole analyzed
    Project loads from its pickle instead of rebuilding — that is what
    makes a warm ``--paths`` pre-commit run sub-second."""
    from elasticdl_tpu.tools.edlint.project import (
        Project,
        load_contexts,
        load_project_cache,
        save_project_cache,
        tree_digest,
    )
    from elasticdl_tpu.tools.edlint.rules import RULES

    rules = [
        r for r in RULES if rule_ids is None or r.id in rule_ids
    ]
    paths = list(iter_source_files(root))
    cached = None
    digest = None
    if use_cache:
        digest = tree_digest(root, paths)
        cached = load_project_cache(root, digest)
    if cached is not None:
        contexts, base_broken, project = cached
    else:
        contexts, base_broken, _stats = load_contexts(
            root, paths, use_cache=use_cache
        )
        project = Project(contexts)
    broken = list(base_broken)
    targets = sorted(contexts)
    if only_paths is not None:
        only = set(only_paths)
        targets = [rel for rel in targets if rel in only]
        for rel in sorted(only - set(contexts)):
            broken.append(
                (rel, "--paths target not in the scan scope")
            )
    findings = []
    for rel in targets:
        ctx = contexts[rel]
        ctx.project = project
        for rule in rules:
            findings.extend(rule.check(ctx))
    findings.sort(key=lambda f: (f.path, f.lineno, f.rule))
    if use_cache and cached is None:
        # save AFTER the rules ran: the lazy analyses they forced
        # (R8 summaries, R5 chains, the R11 lock graph) ride along,
        # so the next run's rule pass is warm too
        save_project_cache(root, digest, contexts, base_broken, project)
    return findings, broken, project


def scan(root, rule_ids=None, use_cache=True, only_paths=None):
    """Back-compat wrapper over :func:`scan_project`: (findings,
    broken) only."""
    findings, broken, _project = scan_project(
        root,
        rule_ids=rule_ids,
        use_cache=use_cache,
        only_paths=only_paths,
    )
    return findings, broken


def apply_ratchet(findings, allow=None):
    """Split findings into (violations, counts, allowed).

    ``allow`` is ``{rule_id: {path: {"max": n, "reason": str}}}``. Per
    (rule, file) the first ``max`` findings in line order are
    suppressed as consciously-allowlisted; everything past the budget
    is a violation. ``counts`` maps (rule, path) -> total occurrences
    (the numbers ``--stale`` compares budgets against).
    """
    if allow is None:
        from elasticdl_tpu.tools.edlint.ratchet import ALLOW

        allow = ALLOW
    counts = {}
    violations = []
    allowed = []
    for f in findings:
        key = (f.rule, f.path)
        counts[key] = counts.get(key, 0) + 1
        budget = allow.get(f.rule, {}).get(f.path, {}).get("max", 0)
        if counts[key] <= budget:
            allowed.append(f)
        else:
            violations.append(f)
    return violations, counts, allowed


def stale_entries(counts, allow=None):
    """Ratchet entries whose budget exceeds current use — the ratchet
    can (and should) shrink to meet the code."""
    if allow is None:
        from elasticdl_tpu.tools.edlint.ratchet import ALLOW

        allow = ALLOW
    stale = []
    for rule_id, files in sorted(allow.items()):
        for path, entry in sorted(files.items()):
            used = counts.get((rule_id, path), 0)
            if used < entry.get("max", 0):
                stale.append((rule_id, path, used, entry["max"]))
    return stale


def run(root, rule_ids=None, allow=None, use_cache=True):
    """(violations, counts, broken) for ``root`` after the ratchet."""
    findings, broken = scan(root, rule_ids=rule_ids, use_cache=use_cache)
    violations, counts, _ = apply_ratchet(findings, allow=allow)
    return violations, counts, broken


def _default_root():
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


def main(argv=None):
    from elasticdl_tpu.tools.edlint.rules import RULES

    parser = argparse.ArgumentParser(
        prog="edlint",
        description="AST-based concurrency & jit-purity analyzer "
        "(docs/static_analysis.md)",
    )
    parser.add_argument(
        "--root",
        default=_default_root(),
        help="repo root to scan (default: this package's repo)",
    )
    parser.add_argument(
        "--rules",
        default=None,
        help="comma-separated rule ids to run (default: all)",
    )
    parser.add_argument(
        "--list-rules",
        action="store_true",
        help="print the rule catalog and exit",
    )
    parser.add_argument(
        "--stale",
        action="store_true",
        help="also report ratchet entries wider than current use "
        "(the ratchet only shrinks)",
    )
    parser.add_argument(
        "--json",
        action="store_true",
        dest="as_json",
        help="machine-readable findings on stdout "
        "(file/line/rule/message/ratchet-state; exit code unchanged)",
    )
    parser.add_argument(
        "--no-cache",
        action="store_true",
        help="bypass the mtime-keyed AST cache "
        "(~/.cache/edlint/ast-<root-hash>.pkl): re-parse every file "
        "and do not write the cache back",
    )
    parser.add_argument(
        "--paths",
        nargs="+",
        default=None,
        metavar="FILE",
        help="incremental mode: run rules only on the named files "
        "(absolute or repo-relative); resolution still spans the "
        "whole tree through the cached Project, so cross-file "
        "findings in the named files stay correct — a warm-cache "
        "pre-commit run is sub-second",
    )
    parser.add_argument(
        "--lock-coverage",
        default=None,
        metavar="EXPORT",
        help="cross-validate a locktrace JSONL edge export against "
        "the R11 static lock graph: a dynamically witnessed edge "
        "missing from the static graph means the summaries are "
        "unsound (exit 1); also reports which static edges no test "
        "has exercised",
    )
    args = parser.parse_args(argv)
    if args.list_rules:
        for rule in RULES:
            print("%s %-18s %s" % (rule.id, rule.name, rule.doc))
        return 0
    rule_ids = (
        tuple(r.strip() for r in args.rules.split(",") if r.strip())
        if args.rules
        else None
    )
    only_paths = None
    if args.paths is not None:
        only_paths = [
            os.path.relpath(os.path.abspath(p), args.root).replace(
                os.sep, "/"
            )
            for p in args.paths
        ]
    findings, broken, project = scan_project(
        args.root,
        rule_ids=rule_ids,
        use_cache=not args.no_cache,
        only_paths=only_paths,
    )
    violations, counts, allowed = apply_ratchet(findings)
    # scope the stale check to the rules (and, with --paths, files)
    # that actually ran: a subset run has zero counts for everything
    # else and must not read their budgets as slack
    stale = (
        [
            s
            for s in stale_entries(counts)
            if (rule_ids is None or s[0] in rule_ids)
            and (only_paths is None or s[1] in only_paths)
        ]
        if args.stale
        else []
    )
    # lock-graph stats + the dynamic cross-check ride the R11 graph
    # (already composed and cached when R11 ran; skipped for subset
    # runs that excluded it, unless --lock-coverage asks for it)
    lock_stats = None
    lock_cov = None
    if args.lock_coverage is not None or (
        rule_ids is None or "R11" in rule_ids
    ):
        graph = project.lock_graph()
        lock_stats = graph.stats()
        if args.lock_coverage is not None:
            from elasticdl_tpu.tools.edlint.lockgraph import (
                coverage,
                load_export,
            )

            lock_cov = coverage(graph, load_export(args.lock_coverage))
            lock_stats["unwitnessed_edges"] = len(lock_cov.unwitnessed)
    rc = 1 if (
        broken
        or violations
        or stale
        or (lock_cov is not None and lock_cov.missing)
    ) else 0
    if args.as_json:
        doc = {
            "root": args.root,
            "rc": rc,
            "findings": [
                {
                    "file": f.path,
                    "line": f.lineno,
                    "rule": f.rule,
                    "message": f.message,
                    "text": f.text,
                    "ratchet_state": state,
                }
                for state, group in (
                    ("violation", violations),
                    ("allowed", allowed),
                )
                for f in group
            ],
            "stale": [
                {"rule": r, "file": p, "used": u, "budget": b}
                for r, p, u, b in stale
            ],
            "broken": [
                {"file": rel, "error": err} for rel, err in broken
            ],
            "counts": [
                {"rule": r, "file": p, "count": c}
                for (r, p), c in sorted(counts.items())
            ],
        }
        if lock_stats is not None:
            doc["lock_graph"] = lock_stats
        if lock_cov is not None:
            from elasticdl_tpu.tools.edlint.lockgraph import lock_name

            doc["lock_coverage"] = {
                "dynamic_edges": lock_cov.dynamic_total,
                "witnessed": len(lock_cov.witnessed),
                "missing": lock_cov.missing,
                "unmatched": len(lock_cov.unmatched),
                "unwitnessed": [
                    {"src": lock_name(s), "dst": lock_name(d)}
                    for s, d in lock_cov.unwitnessed
                ],
            }
        print(json.dumps(doc, indent=1))
        return rc
    if broken:
        print("edlint: %d unparseable file(s)" % len(broken))
        for rel, err in broken:
            print("  %s: %s" % (rel, err))
    if violations:
        print("edlint: %d violation(s)" % len(violations))
        for f in violations:
            print(
                "  %s:%d: [%s] %s: %s"
                % (f.path, f.lineno, f.rule, f.message, f.text)
            )
        print(
            "Fix the pattern (docs/static_analysis.md has the safe "
            "idiom per rule) or consciously extend the ratchet in "
            "elasticdl_tpu/tools/edlint/ratchet.py with a reason, in "
            "the same review."
        )
    if stale:
        print("edlint: %d stale ratchet entr(ies)" % len(stale))
        for rule_id, path, used, budget in stale:
            print(
                "  %s %s: budget %d, used %d — shrink it"
                % (rule_id, path, budget, used)
            )
    if lock_cov is not None:
        from elasticdl_tpu.tools.edlint.lockgraph import lock_name

        print(
            "lock-coverage: %d dynamic edge(s): %d witnessed in the "
            "static graph, %d unmatched (out-of-scope creation "
            "sites), %d MISSING; %d/%d static edge(s) unexercised by "
            "any traced run"
            % (
                lock_cov.dynamic_total,
                len(lock_cov.witnessed),
                len(lock_cov.unmatched),
                len(lock_cov.missing),
                len(lock_cov.unwitnessed),
                lock_stats["edges"],
            )
        )
        for doc in lock_cov.missing:
            print(
                "  UNSOUND: witnessed edge %s -> %s (%s -> %s) is "
                "absent from the static graph — the R8/R11 summaries "
                "missed a path the test suite executed"
                % (
                    doc.get("static_src"),
                    doc.get("static_dst"),
                    doc.get("src_site"),
                    doc.get("dst_site"),
                )
            )
    return rc


if __name__ == "__main__":
    sys.exit(main())
