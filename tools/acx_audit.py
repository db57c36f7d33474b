#!/usr/bin/env python3
"""acx_audit: the cross-layer contract linter (docs/DESIGN.md §18).

The runtime spans five contract surfaces that every PR tends to grow at
once: env knobs, the C API <-> ctypes bindings, the metrics registry,
the flight-recorder event kinds, and the crash-flush signal path. Each
surface has two or more files that must agree (code <-> README, capi.cc
<-> runtime.py, metrics.cc <-> DESIGN.md tables, flightrec.cc <->
acx_doctor.py) and nothing but convention kept them in sync. This tool
turns each convention into an enforced rule:

  knobs        every getenv("ACX_*") site is documented in README.md,
               and every README knob still exists in code
  bindings     every acx_* export in src/api/capi.cc has a ctypes
               declaration (name + arity) in mpi_acx_tpu/runtime.py,
               and vice versa
  registry     every counter/hist/gauge name in the metrics registry
               has a row in DESIGN.md's observability tables, the
               tables name only live entries, and the generic
               consumers (tseries.cc, acx_top.py) still consume them
  flight_kinds every event kind name in flightrec.cc is decodable by
               acx_doctor.py's KNOWN_KINDS table, and vice versa
  journey_kinds every request-journey kind emitted by the serving
               loops (serving.py/disagg.py/kvpage.py via reqlog.emit)
               is declared in mpi_acx_tpu/reqlog.py KINDS and
               decodable by tools/acx_request.py's KINDS table, and
               neither table carries stale rows
  signal_path  functions reachable from the crash-flusher registry
               (trace.cc RegisterCrashFlusher roots) never call a
               denylist of non-async-signal-safe / blocking
               primitives (malloc, fprintf on shared streams,
               blocking lock(), condvar waits, ...)

stdlib-only, like acx_doctor.py / acx_chaos.py. Exit 0 = clean,
1 = violations (one `rule: file:line: message` line each), 2 = the
audit itself could not run (missing surface file, bad allowlist).

Intentional-exception policy lives in tools/audit_allowlist.json; every
entry requires a human-readable reason string (empty reasons are an
error — the allowlist documents debt, it does not hide it).

The signal-path rule is a conservative regex call graph: function
bodies are found by brace matching, callees by bare name (so virtual
dispatch and same-named methods conflate — deliberately: a flusher
must be safe against every plausible resolution). `static x = []{...}()`
initializer lambdas are excluded from the scan: they run exactly once,
at first call on a normal (non-signal) path, and every crash flusher is
registered *from* such a latch — by the time a flusher can run, the
latch has already run. Indirect calls the graph cannot see (function
pointers) are declared as `extra_edges` in the allowlist.
"""

import argparse
import json
import os
import re
import sys

# --------------------------------------------------------------------------
# shared helpers


class AuditError(Exception):
    """The audit itself cannot run (missing file, malformed allowlist)."""


class Violation:
    def __init__(self, rule, path, line, msg):
        self.rule = rule
        self.path = path
        self.line = line
        self.msg = msg

    def __str__(self):
        return "%s: %s:%d: %s" % (self.rule, self.path, self.line, self.msg)

    def as_json(self):
        return {"rule": self.rule, "file": self.path, "line": self.line,
                "msg": self.msg}


def read_file(root, rel):
    path = os.path.join(root, rel)
    if not os.path.isfile(path):
        raise AuditError("required file missing: %s" % rel)
    with open(path, "r", errors="replace") as f:
        return f.read()


def line_of(text, offset):
    return text.count("\n", 0, offset) + 1


def strip_c(text, strip_strings=True):
    """Blank out C/C++ comments (and optionally string/char literals),
    preserving newlines so offsets still map to the right line."""
    out = []
    i, n = 0, len(text)
    while i < n:
        c = text[i]
        if c == "/" and i + 1 < n and text[i + 1] == "/":
            j = text.find("\n", i)
            j = n if j < 0 else j
            out.append(" " * (j - i))
            i = j
        elif c == "/" and i + 1 < n and text[i + 1] == "*":
            j = text.find("*/", i + 2)
            j = n if j < 0 else j + 2
            out.append(re.sub(r"[^\n]", " ", text[i:j]))
            i = j
        elif strip_strings and c in "\"'":
            q = c
            j = i + 1
            while j < n:
                if text[j] == "\\":
                    j += 2
                    continue
                if text[j] == q:
                    j += 1
                    break
                j += 1
            out.append(q + " " * (j - i - 2) + q if j - i >= 2 else text[i:j])
            i = j
        else:
            out.append(c)
            i += 1
    return "".join(out)


def match_brace(text, open_pos, open_ch="{", close_ch="}"):
    """Index one past the brace matching text[open_pos]; -1 if unbalanced."""
    depth = 0
    for i in range(open_pos, len(text)):
        if text[i] == open_ch:
            depth += 1
        elif text[i] == close_ch:
            depth -= 1
            if depth == 0:
                return i + 1
    return -1


def extract_array_strings(text, array_name):
    """Quoted strings inside `array_name[] = { ... }` (comment-stripped
    text must NOT have strings stripped). Returns (names, line)."""
    m = re.search(re.escape(array_name) + r"\s*\[\s*\]\s*=\s*\{", text)
    if not m:
        raise AuditError("array %s not found" % array_name)
    start = text.index("{", m.start())
    end = match_brace(text, start)
    if end < 0:
        raise AuditError("array %s: unbalanced braces" % array_name)
    names = re.findall(r'"([^"]*)"', text[start:end])
    return names, line_of(text, m.start())


# --------------------------------------------------------------------------
# allowlist

ALLOWLIST_REL = os.path.join("tools", "audit_allowlist.json")


def load_allowlist(root, explicit_path=None):
    path = explicit_path or os.path.join(root, ALLOWLIST_REL)
    if not os.path.isfile(path):
        raise AuditError("allowlist missing: %s" % path)
    try:
        with open(path, "r") as f:
            allow = json.load(f)
    except ValueError as e:
        raise AuditError("allowlist %s: invalid JSON: %s" % (path, e))
    # Every exception must carry a nonempty reason. extra_edges values are
    # lists of callees; acx_top_deps is a plain list — everything else maps
    # name -> reason.
    for section, table in sorted(allow.items()):
        if section.startswith("_"):
            continue
        if not isinstance(table, dict):
            raise AuditError("allowlist: section %r must be an object"
                            % section)
        for key, val in sorted(table.items()):
            if key in ("extra_edges", "acx_top_deps") or key.startswith("_"):
                continue
            if isinstance(val, dict):
                for name, reason in sorted(val.items()):
                    if not (isinstance(reason, str) and reason.strip()):
                        raise AuditError(
                            "allowlist: %s.%s.%s needs a nonempty reason"
                            % (section, key, name))
            elif not (isinstance(val, str) and val.strip()):
                raise AuditError("allowlist: %s.%s needs a nonempty reason"
                                % (section, key))
    return allow


# --------------------------------------------------------------------------
# rule 1: knob audit

KNOB_DIRS = ("src", "include", "tools", "mpi_acx_tpu")
KNOB_RE = r"(?:ACX|MPIACX)_[A-Z0-9_]+"
# Read/write sites that prove a knob is live in code. Subscripts cover both
# os.environ["X"] reads and the env-dict writes acxrun uses to arm children.
# The C form also matches the repo's env-reading helpers (fault.cc Env(),
# flightrec.cc EnvMsToNs(), ...): any *getenv/Env* function taking the
# knob name as its first string literal argument.
C_KNOB_REF = re.compile(r'\b(?:\w*getenv|Env\w*)\(\s*"(%s)"' % KNOB_RE)
PY_KNOB_REF = re.compile(
    r'(?:getenv|environ\.get)\(\s*"(%s)"|\[\s*"(%s)"\s*\]'
    % (KNOB_RE, KNOB_RE))


def iter_source_files(root, dirs, exts):
    for d in dirs:
        top = os.path.join(root, d)
        if not os.path.isdir(top):
            continue
        for dirpath, _dirnames, filenames in os.walk(top):
            for fn in sorted(filenames):
                if os.path.splitext(fn)[1] in exts:
                    full = os.path.join(dirpath, fn)
                    yield os.path.relpath(full, root), full


def audit_knobs(root, allow):
    violations = []
    knob_allow = allow.get("knobs", {})
    test_only = knob_allow.get("test_only", {})
    not_knobs = knob_allow.get("not_knobs", {})
    # Documented knobs whose only read sites are outside the audited dirs
    # (e.g. tests/disagg_worker.py). Still real knobs — just consumed
    # beyond the surface this rule scans.
    external = knob_allow.get("external_readers", {})

    refs = {}  # name -> (relpath, line) of first reference
    for rel, full in iter_source_files(root, KNOB_DIRS,
                                       {".c", ".cc", ".h", ".py"}):
        if rel == ALLOWLIST_REL:
            continue
        with open(full, "r", errors="replace") as f:
            text = f.read()
        pat = PY_KNOB_REF if rel.endswith(".py") else C_KNOB_REF
        scan = text if rel.endswith(".py") else strip_c(text,
                                                        strip_strings=False)
        for m in pat.finditer(scan):
            name = m.group(1) or (m.group(2) if pat is PY_KNOB_REF else None)
            if name and name not in refs:
                refs[name] = (rel, line_of(scan, m.start()))

    readme = read_file(root, "README.md")
    documented = {}  # name -> first README line
    for m in re.finditer(r"\b(%s)\b" % KNOB_RE, readme):
        documented.setdefault(m.group(1), line_of(readme, m.start()))

    for name in sorted(set(refs) - set(documented) - set(test_only)):
        rel, line = refs[name]
        violations.append(Violation(
            "knobs", rel, line,
            "env knob %s is read in code but has no row/mention in "
            "README.md (document it, or allowlist it under "
            "knobs.test_only with a reason)" % name))
    for name in sorted(set(documented) - set(refs) - set(not_knobs)
                       - set(external)):
        violations.append(Violation(
            "knobs", "README.md", documented[name],
            "README documents %s but no code under %s references it "
            "(delete the row; allowlist under knobs.not_knobs if it is "
            "not an env knob, or knobs.external_readers if it is read "
            "outside the audited dirs)" % (name, "/".join(KNOB_DIRS))))
    return violations


# --------------------------------------------------------------------------
# rule 2: binding audit

CAPI_REL = os.path.join("src", "api", "capi.cc")
RUNTIME_REL = os.path.join("mpi_acx_tpu", "runtime.py")
CAPI_DEF = re.compile(
    r"^[A-Za-z_][\w \t\*]*?\b(acx_\w+)\s*\(([^)]*)\)\s*\{",
    re.MULTILINE | re.DOTALL)


def c_arity(params):
    params = params.strip()
    if params in ("", "void"):
        return 0
    return params.count(",") + 1


def split_top_level(text):
    """Split on commas not nested in (), [], {}. Empty text -> []."""
    parts, depth, cur = [], 0, []
    for c in text:
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        if c == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(c)
    tail = "".join(cur).strip()
    if tail:
        parts.append(tail)
    return [p for p in (q.strip() for q in parts) if p]


def audit_bindings(root, allow):
    violations = []
    bind_allow = allow.get("bindings", {})
    unbound_ok = bind_allow.get("unbound_exports", {})

    capi = strip_c(read_file(root, CAPI_REL))
    exports = {}  # name -> (line, arity)
    for m in CAPI_DEF.finditer(capi):
        exports[m.group(1)] = (line_of(capi, m.start(1)),
                               c_arity(m.group(2)))

    runtime = read_file(root, RUNTIME_REL)
    # Strip full-line comments only; ctypes decls never share a line with
    # meaningful '#' usage here.
    runtime = re.sub(r"(?m)^\s*#.*$", "", runtime)
    declared = {}  # name -> line of first decl
    argtypes = {}  # name -> (line, arity)
    for m in re.finditer(r"_lib\.(acx_\w+)\.restype", runtime):
        declared.setdefault(m.group(1), line_of(runtime, m.start()))
    for m in re.finditer(r"_lib\.(acx_\w+)\.argtypes\s*=\s*\[", runtime):
        name = m.group(1)
        declared.setdefault(name, line_of(runtime, m.start()))
        start = runtime.index("[", m.end() - 1)
        end = match_brace(runtime, start, "[", "]")
        if end < 0:
            violations.append(Violation(
                "bindings", RUNTIME_REL, line_of(runtime, m.start()),
                "%s.argtypes: unbalanced bracket" % name))
            continue
        argtypes[name] = (line_of(runtime, m.start()),
                          len(split_top_level(runtime[start + 1:end - 1])))

    for name in sorted(set(exports) - set(declared) - set(unbound_ok)):
        line, arity = exports[name]
        violations.append(Violation(
            "bindings", CAPI_REL, line,
            "C export %s (arity %d) has no ctypes declaration in %s "
            "(add restype/argtypes, or allowlist under "
            "bindings.unbound_exports with a reason)"
            % (name, arity, RUNTIME_REL)))
    for name in sorted(set(declared) - set(exports)):
        violations.append(Violation(
            "bindings", RUNTIME_REL, declared[name],
            "ctypes declaration for %s has no matching export in %s "
            "(stale binding?)" % (name, CAPI_REL)))
    for name in sorted(set(exports) & set(declared)):
        _line, arity = exports[name]
        if name in argtypes:
            pline, parity = argtypes[name]
            if parity != arity:
                violations.append(Violation(
                    "bindings", RUNTIME_REL, pline,
                    "%s: argtypes lists %d parameter(s) but the C export "
                    "takes %d" % (name, parity, arity)))
        elif arity != 0:
            violations.append(Violation(
                "bindings", RUNTIME_REL, declared[name],
                "%s: C export takes %d parameter(s) but runtime.py sets "
                "no argtypes (ctypes would guess)" % (name, arity)))
    return violations


# --------------------------------------------------------------------------
# rule 3: registry audit

METRICS_CC_REL = os.path.join("src", "core", "metrics.cc")
TSERIES_REL = os.path.join("src", "core", "tseries.cc")
TOP_REL = os.path.join("tools", "acx_top.py")
DESIGN_REL = os.path.join("docs", "DESIGN.md")
TABLE_BEGIN = "<!-- acx-audit:registry-table:begin -->"
TABLE_END = "<!-- acx-audit:registry-table:end -->"
# Generic-consumption tokens: tseries.cc iterates the whole registry by
# construction. If a refactor replaces the generic loop with a
# hand-maintained list, the per-name guarantee is gone and this rule must
# be extended — so their disappearance is itself a violation.
TSERIES_TOKENS = ("kNumCounters", "CounterName", "IsGauge", "kNumHists",
                  "HistName")


def parse_design_tables(design):
    """Backticked names in table rows between the audit markers.
    Returns (dict name -> line, marker_line)."""
    begin = design.find(TABLE_BEGIN)
    end = design.find(TABLE_END)
    if begin < 0 or end < 0 or end < begin:
        raise AuditError(
            "%s: registry table markers (%s ... %s) missing"
            % (DESIGN_REL, TABLE_BEGIN, TABLE_END))
    rows = {}
    offset = begin
    for rawline in design[begin:end].split("\n"):
        stripped = rawline.strip()
        if stripped.startswith("|"):
            m = re.match(r"\|\s*`([a-z0-9_]+)`", stripped)
            if m:
                rows.setdefault(m.group(1), line_of(design, offset))
        offset += len(rawline) + 1
    return rows, line_of(design, begin)


def audit_registry(root, allow):
    violations = []
    reg_allow = allow.get("registry", {})
    top_deps = reg_allow.get("acx_top_deps", [])

    metrics = strip_c(read_file(root, METRICS_CC_REL), strip_strings=False)
    counters, counters_line = extract_array_strings(metrics, "kCounterName")
    hists, _ = extract_array_strings(metrics, "kHistName")
    gm = re.search(r'\\"gauges\\":\[([^\]]*)\]', metrics)
    gauges = re.findall(r'\\"([a-z0-9_]+)\\"', gm.group(1)) if gm else []
    registry = set(counters) | set(hists)

    for g in gauges:
        if g not in counters:
            violations.append(Violation(
                "registry", METRICS_CC_REL, counters_line,
                'gauge "%s" (SnapshotString "gauges" list) is not a '
                "registered counter name" % g))

    design = read_file(root, DESIGN_REL)
    rows, table_line = parse_design_tables(design)
    for name in sorted(registry - set(rows)):
        kind = "histogram" if name in hists else \
               ("gauge" if name in gauges else "counter")
        violations.append(Violation(
            "registry", DESIGN_REL, table_line,
            "registry %s \"%s\" (%s) has no row in the observability "
            "table" % (kind, name, METRICS_CC_REL)))
    for name in sorted(set(rows) - registry):
        violations.append(Violation(
            "registry", DESIGN_REL, rows[name],
            "observability table row `%s` names no registry entry in %s "
            "(stale doc row?)" % (name, METRICS_CC_REL)))

    tseries = read_file(root, TSERIES_REL)
    for tok in TSERIES_TOKENS:
        if tok not in tseries:
            violations.append(Violation(
                "registry", TSERIES_REL, 1,
                "generic registry consumption token %s missing from "
                "tseries.cc — if the sampler no longer iterates the whole "
                "registry, extend the registry rule (DESIGN.md §18)"
                % tok))

    top = read_file(root, TOP_REL)
    for name in top_deps:
        if name not in registry:
            violations.append(Violation(
                "registry", ALLOWLIST_REL, 1,
                "registry.acx_top_deps names \"%s\" which is not a "
                "registry entry (renamed counter?)" % name))
        elif '"%s"' % name not in top:
            violations.append(Violation(
                "registry", TOP_REL, 1,
                "acx_top.py no longer references registry counter \"%s\" "
                "its columns depend on (update the column or "
                "registry.acx_top_deps)" % name))
    return violations


# --------------------------------------------------------------------------
# rule 4: flight-kind audit

FLIGHTREC_REL = os.path.join("src", "core", "flightrec.cc")
DOCTOR_REL = os.path.join("tools", "acx_doctor.py")


def audit_flight_kinds(root, allow):
    del allow  # no exceptions: every kind must be decodable
    violations = []
    flight = strip_c(read_file(root, FLIGHTREC_REL), strip_strings=False)
    kinds, kinds_line = extract_array_strings(flight, "kKindNames")

    doctor = read_file(root, DOCTOR_REL)
    m = re.search(r"KNOWN_KINDS\s*=\s*\{", doctor)
    if not m:
        raise AuditError("%s: KNOWN_KINDS table not found" % DOCTOR_REL)
    start = doctor.index("{", m.start())
    end = match_brace(doctor, start)
    if end < 0:
        raise AuditError("%s: KNOWN_KINDS: unbalanced braces" % DOCTOR_REL)
    table_line = line_of(doctor, m.start())
    known = {}
    offset = start
    for km in re.finditer(r'"([a-z0-9_]+)"', doctor[start:end]):
        known.setdefault(km.group(1), line_of(doctor, start + km.start()))

    for name in sorted(set(kinds) - set(known)):
        violations.append(Violation(
            "flight_kinds", FLIGHTREC_REL, kinds_line,
            'event kind "%s" is not decodable by acx_doctor.py '
            "(add it to KNOWN_KINDS at %s:%d)"
            % (name, DOCTOR_REL, table_line)))
    for name in sorted(set(known) - set(kinds)):
        violations.append(Violation(
            "flight_kinds", DOCTOR_REL, known[name],
            'KNOWN_KINDS entry "%s" matches no kind in %s kKindNames '
            "(stale table row?)" % (name, FLIGHTREC_REL)))
    return violations


# --------------------------------------------------------------------------
# rule 4b: journey-kind audit (the flight_kinds rule, one layer up: the
# request-journey plane of DESIGN.md §20 instead of the flight recorder)

REQLOG_REL = os.path.join("mpi_acx_tpu", "reqlog.py")
REQUEST_TOOL_REL = os.path.join("tools", "acx_request.py")
JOURNEY_EMITTERS = (
    os.path.join("mpi_acx_tpu", "models", "serving.py"),
    os.path.join("mpi_acx_tpu", "models", "disagg.py"),
    os.path.join("mpi_acx_tpu", "models", "kvpage.py"),
)


def _brace_table(text, head_re, rel, what, key_re=r'"([a-z0-9_]+)"'):
    """Quoted names inside the first brace block after head_re.
    Returns (dict name -> line, header_line)."""
    m = re.search(head_re, text)
    if not m:
        raise AuditError("%s: %s not found" % (rel, what))
    start = text.index("{", m.start())
    end = match_brace(text, start)
    if end < 0:
        raise AuditError("%s: %s: unbalanced braces" % (rel, what))
    names = {}
    for km in re.finditer(key_re, text[start:end]):
        names.setdefault(km.group(1), line_of(text, start + km.start()))
    return names, line_of(text, m.start())


def audit_journey_kinds(root, allow):
    del allow  # no exceptions: every emitted kind must be decodable
    violations = []

    # The literal kinds the serving loops emit (first site per kind).
    emitted = {}
    for rel in JOURNEY_EMITTERS:
        text = read_file(root, rel)
        for m in re.finditer(r'reqlog\.emit\(\s*"([a-z0-9_]+)"', text):
            emitted.setdefault(m.group(1), (rel, line_of(text, m.start())))

    # The declared vocabulary (reqlog.KINDS frozenset).
    vocab, vocab_line = _brace_table(
        read_file(root, REQLOG_REL),
        r"KINDS\s*=\s*frozenset\(\s*\{", REQLOG_REL, "KINDS frozenset")
    # The offline decode table (acx_request.KINDS dict — keys only; the
    # values are free-text descriptions).
    decode, decode_line = _brace_table(
        read_file(root, REQUEST_TOOL_REL),
        r"(?m)^KINDS\s*=\s*\{", REQUEST_TOOL_REL, "KINDS decode table",
        key_re=r'(?m)^\s*"([a-z0-9_]+)"\s*:')

    for name in sorted(set(emitted) - set(vocab)):
        rel, line = emitted[name]
        violations.append(Violation(
            "journey_kinds", rel, line,
            'journey kind "%s" is emitted but not declared in %s KINDS '
            "(line %d)" % (name, REQLOG_REL, vocab_line)))
    for name in sorted(set(emitted) - set(decode)):
        rel, line = emitted[name]
        violations.append(Violation(
            "journey_kinds", rel, line,
            'journey kind "%s" is emitted but not decodable by %s KINDS '
            "(line %d) — acx_request.py would warn it unknown at merge "
            "time" % (name, REQUEST_TOOL_REL, decode_line)))
    for name in sorted(set(vocab) - set(emitted)):
        violations.append(Violation(
            "journey_kinds", REQLOG_REL, vocab[name],
            'KINDS declares "%s" but no serving loop (%s) emits it '
            "(stale vocabulary entry?)"
            % (name, ", ".join(JOURNEY_EMITTERS))))
    for name in sorted(set(vocab) - set(decode)):
        violations.append(Violation(
            "journey_kinds", REQLOG_REL, vocab[name],
            'KINDS declares "%s" but %s cannot decode it (add a decode '
            "table row)" % (name, REQUEST_TOOL_REL)))
    for name in sorted(set(decode) - set(vocab)):
        violations.append(Violation(
            "journey_kinds", REQUEST_TOOL_REL, decode[name],
            'decode table row "%s" matches no kind in %s KINDS (stale '
            "row?)" % (name, REQLOG_REL)))
    return violations


# --------------------------------------------------------------------------
# rule 5: signal-path audit

SIGNAL_DIRS = (os.path.join("src", "core"), os.path.join("src", "net"),
               os.path.join("src", "api"), os.path.join("include", "acx"))
CXX_KEYWORDS = frozenset((
    "if", "for", "while", "switch", "catch", "return", "sizeof", "alignof",
    "decltype", "static_assert", "noexcept", "defined", "assert", "new",
    "delete", "throw", "else", "do", "case", "not"))
# A function definition: name(params) [const] [noexcept] [ACX_*(...)]...
# [: init-list] { — params may span lines but contain no top-level ')'.
FUNC_DEF = re.compile(
    r"\b([A-Za-z_]\w*)\s*\(([^(){};]*(?:\([^()]*\)[^(){};]*)*)\)\s*"
    r"(?:const\b\s*)?(?:noexcept\b\s*)?"
    r"(?:ACX_[A-Z_]+\s*\([^()]*\)\s*)*"
    r"(?::\s*[^;{]*?)?\{")
CALLEE = re.compile(r"\b([A-Za-z_]\w*)\s*\(")
STATIC_IIFE = re.compile(
    r"static\s+[^;{}=]*=\s*\[[^\]]*\]\s*(?:\([^)]*\)\s*)?"
    r"(?:->\s*[\w:<>\*&\s]+?)?\s*\{")

# (regex, label). Applied to comment/string-stripped bodies of every
# crash-flush-reachable function. `new` is deliberately absent (flagging
# it would force assume_safe noise for container growth the flush paths
# avoid by construction); std::string member ops are a documented
# limitation (DESIGN.md §18).
DENYLIST = (
    (re.compile(r"\b(?:malloc|calloc|realloc|free)\s*\("),
     "heap allocator call (not async-signal-safe)"),
    (re.compile(r"\bfprintf\s*\(\s*(?:stderr|stdout)\b"),
     "fprintf on a shared stdio stream (takes the stream lock; "
     "use trace::WriteErrNote)"),
    (re.compile(r"(?<!\w)printf\s*\("),
     "printf (shared stdio stream)"),
    (re.compile(r"\bstd::lock_guard\s*<"),
     "blocking std::lock_guard (use acx::TryMutexLock on flush paths)"),
    (re.compile(r"(?<!Try)\bMutexLock\s*\("),
     "blocking acx::MutexLock (use TryMutexLock on flush paths)"),
    (re.compile(r"\.\s*lock\s*\("),
     "blocking .lock() (use try_lock on flush paths)"),
    (re.compile(r"\bstd::call_once\b"),
     "std::call_once (blocks on a concurrent in-flight initializer)"),
    (re.compile(r"\bsleep_(?:for|until)\s*\("),
     "thread sleep on a flush path"),
    (re.compile(r"\bstd::to_string\s*\("),
     "std::to_string allocates (use snprintf into a stack buffer)"),
    (re.compile(r"\.\s*wait(?:_for|_until)?\s*\("),
     "condition-variable wait on a flush path"),
)

ROOT_RE = re.compile(r"RegisterCrashFlusher\s*\(\s*&?(?:\w+::)*(\w+)")


def strip_static_iifes(body):
    """Blank out `static x = []{...}()` latch bodies (run once, on a
    normal path, before any flusher can fire)."""
    out = body
    pos = 0
    while True:
        m = STATIC_IIFE.search(out, pos)
        if not m:
            return out
        # the regex anchors on the lambda's opening body brace (last char)
        start = m.end() - 1
        end = match_brace(out, start)
        if end < 0:
            return out
        out = out[:start + 1] + re.sub(r"[^\n]", " ",
                                       out[start + 1:end - 1]) + out[end - 1:]
        pos = end


def extract_functions(text):
    """[(name, body_start_offset, body_text)] from comment/string-stripped
    C++ source. Bare names: overloads and same-named methods conflate."""
    funcs = []
    for m in FUNC_DEF.finditer(text):
        name = m.group(1)
        if name in CXX_KEYWORDS:
            continue
        open_pos = m.end() - 1
        close = match_brace(text, open_pos)
        if close < 0:
            continue
        funcs.append((name, open_pos, text[open_pos:close]))
    return funcs


def audit_signal_path(root, allow):
    violations = []
    sig_allow = allow.get("signal_path", {})
    assume_safe = sig_allow.get("assume_safe", {})
    extra_edges = sig_allow.get("extra_edges", {})

    defs = {}   # bare name -> [(relpath, body_offset, stripped_body)]
    roots = set()
    texts = {}  # relpath -> stripped text (for line numbers)
    for rel, full in iter_source_files(root, SIGNAL_DIRS, {".cc", ".h"}):
        with open(full, "r", errors="replace") as f:
            raw = f.read()
        text = strip_c(raw)
        texts[rel] = text
        for m in ROOT_RE.finditer(text):
            # Skip the registrar's own prototype/definition, which matches
            # the pattern with its parameter type ("void (*fn)()").
            if m.group(1) not in ("void",) and m.group(1) not in CXX_KEYWORDS:
                roots.add(m.group(1))
        for name, off, body in extract_functions(text):
            defs.setdefault(name, []).append(
                (rel, off, strip_static_iifes(body)))

    if not roots:
        # No crash-flusher registry in the scanned tree (fixture trees may
        # stub it): nothing is reachable, nothing to check.
        return violations

    # BFS over bare-name call edges from the flusher roots.
    parent = {r: None for r in roots}
    queue = sorted(roots)
    reachable = set()
    while queue:
        name = queue.pop(0)
        if name in reachable or name in assume_safe:
            continue
        reachable.add(name)
        for callee in extra_edges.get(name, []):
            if callee not in parent:
                parent[callee] = name
                queue.append(callee)
        for _rel, _off, body in defs.get(name, []):
            for cm in CALLEE.finditer(body):
                callee = cm.group(1)
                if callee in CXX_KEYWORDS or callee == name:
                    continue
                if callee in defs and callee not in parent:
                    parent[callee] = name
                    queue.append(callee)

    def chain(name):
        links = []
        while name is not None:
            links.append(name)
            name = parent.get(name)
        return " <- ".join(links)

    for name in sorted(reachable):
        for rel, off, body in defs.get(name, []):
            for pat, label in DENYLIST:
                for dm in pat.finditer(body):
                    violations.append(Violation(
                        "signal_path", rel,
                        line_of(texts[rel], off + dm.start()),
                        "%s in %s(), reachable from a crash flusher "
                        "(%s)" % (label, name, chain(name))))
    return violations


# --------------------------------------------------------------------------
# driver

RULES = (
    ("knobs", audit_knobs),
    ("bindings", audit_bindings),
    ("registry", audit_registry),
    ("flight_kinds", audit_flight_kinds),
    ("journey_kinds", audit_journey_kinds),
    ("signal_path", audit_signal_path),
)


def find_root(start):
    d = os.path.abspath(start)
    while True:
        if os.path.isfile(os.path.join(d, "README.md")) and \
           os.path.isdir(os.path.join(d, "src")):
            return d
        up = os.path.dirname(d)
        if up == d:
            return None
        d = up


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="cross-layer contract linter (DESIGN.md §18)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: walk up from this script)")
    ap.add_argument("--allowlist", default=None,
                    help="allowlist JSON (default: <root>/%s)"
                    % ALLOWLIST_REL)
    ap.add_argument("--rule", action="append", default=None,
                    choices=[name for name, _ in RULES],
                    help="run only this rule (repeatable)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name, fn in RULES:
            print("%-14s %s" % (name, (fn.__doc__ or "").strip()))
        return 0

    root = args.root or find_root(os.path.dirname(os.path.abspath(__file__)))
    if root is None or not os.path.isdir(root):
        print("acx_audit: cannot locate repo root (pass --root)",
              file=sys.stderr)
        return 2

    try:
        allow = load_allowlist(root, args.allowlist)
        selected = args.rule or [name for name, _ in RULES]
        violations = []
        counts = {}
        for name, fn in RULES:
            if name not in selected:
                continue
            found = fn(root, allow)
            counts[name] = len(found)
            violations.extend(found)
    except AuditError as e:
        if args.json:
            print(json.dumps({"ok": False, "error": str(e)}))
        else:
            print("acx_audit: error: %s" % e, file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({
            "ok": not violations,
            "rules": counts,
            "violations": [v.as_json() for v in violations],
        }, indent=2, sort_keys=True))
    else:
        for v in violations:
            print(v)
        if violations:
            bad = sorted(r for r, n in counts.items() if n)
            print("acx_audit: %d violation(s) in rule(s): %s"
                  % (len(violations), ", ".join(bad)), file=sys.stderr)
        else:
            print("acx_audit: clean (%s)"
                  % ", ".join("%s=0" % r for r, _n in sorted(counts.items())),
                  file=sys.stderr)
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
