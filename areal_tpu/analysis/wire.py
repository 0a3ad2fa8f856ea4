"""AR3xx — cross-component wire contracts & observability drift.

The fleet is four processes (trainer, router, decode replicas, supervisor)
stitched together by STRING-KEYED contracts: HTTP route paths, fault-seam
names, metric keys the router/supervisor poll out of `/metrics`,
`_GUARDED_BY` registry entries, and config knobs mirrored into argparse
flags. None of these are checked by the type system — a typo'd seam
pattern silently never fires, a renamed metric silently blinds the
autoscaler, a dead endpoint rots until an operator needs it. The AR3xx
family checks them statically, with the same pure-AST machinery (no
imports, no execution) as AR1xx/AR2xx.

AR301 — route pairing. Server-side registrations
  (`app.router.add_get("/x", h)` and friends) are matched against
  client-side path literals: `*_ENDPOINT = "/x"` constants, string and
  f-string arguments of HTTP-ish calls (`arequest_with_retry`,
  `aget_with_retry`, `_http_get`, ...; query strings are stripped, so
  `f"/kv_recv?xid={xid}"` pairs with the `/kv_recv` registration).
  A client path with no registration anywhere in the analyzed set is an
  unregistered-endpoint finding; a registration in `launcher/` that no
  client reaches is a dead-endpoint finding unless the line carries
  `# wire: external` (an ops/bench surface consumed outside the tree —
  the annotation IS the declared contract). Both directions are skipped
  when the analyzed set harvested no registrations at all, so a
  client-only sweep (`tools/lint.sh --all` over `tools/`) stays quiet.

AR302 — fault-seam validity. Every `fire/afire/tear("<seam>", ...)`
  string constant is a real seam; every `FaultPoint(site=<pat>)` /
  `{"site": <pat>}` literal is an fnmatch pattern. A pattern matching
  zero harvested seams is a plan that silently never fires. A seam name
  fired from two different modules is a collision: one fnmatch pattern
  now perturbs two unrelated boundaries. Pattern checks are skipped when
  the analyzed set harvested no seams (plans live in bench/tests; seams
  live in the tree — only a combined or self-contained run can judge).

AR303 — metrics contract. Producer keys are harvested from metrics
  producers — functions named `get_metrics` / `_health` / `*_metrics`, or
  functions/assignments annotated `# metrics-producer` (for helpers and
  entry templates, like the router's breaker defaultdict, whose dicts
  ride inside `/metrics`) — plus the initializer keys of
  `self.*_stats` / `self.*_counters` / `self.*_gauges` dicts, which are
  exported wholesale via `**` splats. Consumers are the module-level
  `*_KEYS` tuples (the router's `_PRESSURE_KEYS` pressure contract) and
  functions annotated `# metrics-consumer`, whose string-keyed `.get()` /
  subscript reads must name a produced key. Locally: a write to
  `self._x_stats["k"]` where `k` is not in the dict's initializer is
  counter drift — the increment lands in a key the export never shows
  until first hit, and usually means a renamed metric.

AR304 — `_GUARDED_BY` staleness. A registry entry `"Class.attr"` whose
  class IS defined in the module but whose attr is never touched by the
  class is a leftover from a refactor: it waives AR101 for an attribute
  that no longer exists (the unknown-lock and unknown-class halves are
  AR104's).

AR305 — config-knob drift. argparse flags in `launcher/` servers mirror
  dataclass fields in `api/cli_args.py`; a flag whose dest matches no
  field in the analyzed set has drifted from the knob it mirrors
  (`--tp-size` vs `tensor_parallel_size` is the canonical shape — fix
  with an explicit `dest=`). Flags that are genuinely launcher
  infrastructure (not config mirrors) carry `# knob: launcher-only`;
  `host`/`port` are built-in infra. The `/info` surface is checked the
  same way: `self.config.X` reads inside an `_info` handler must name a
  real field. Skipped when the analyzed set harvested no dataclass
  fields.

Scope: harvesting runs everywhere; the registration-side (dead endpoint),
argparse, and `/info` checks apply only to `launcher/` files — and to
paths containing `fixtures` (the seeded test fixtures), which are always
fully checked. Cross-file findings are pragma-suppressable at their
anchor site like every other rule.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from dataclasses import dataclass, field

from areal_tpu.analysis.concurrency import _guard_registry
from areal_tpu.analysis.core import Finding, SourceFile, call_root

# single-segment endpoint path: "/generate", "/kv_recv" — NOT "/q" alone
# being excluded by shape ("/q" matches), so the call-context filter below
# is what keeps string-suffix literals like `endswith(("/q", "/scale"))`
# out of the client-ref set
_PATH_RE = re.compile(r"^/[a-z_][a-z0-9_]*$")

# callee leaf names that take an endpoint path argument; deliberately NOT
# generic verbs like `get` — `os.environ.get("TMPDIR", "/tmp")` is exactly
# the endpoint-shaped non-endpoint that would poison the pairing
_HTTP_CALLS = {
    "arequest_with_retry",
    "aget_with_retry",
    "wait_server_healthy",
    "_fanout",
    "_http_get",
    "_http_post",
    "http_get",
    "http_post",
}

_ROUTE_ADDERS = {
    "add_get",
    "add_post",
    "add_put",
    "add_delete",
    "add_patch",
    "add_route",
}

_SEAM_ENTRIES = {"fire", "afire", "tear"}

_STATS_SUFFIXES = ("_stats", "_counters", "_gauges")

_WIRE_EXTERNAL_RE = re.compile(r"#\s*wire:\s*external")
_METRICS_PRODUCER_RE = re.compile(r"#\s*metrics-producer")
_METRICS_CONSUMER_RE = re.compile(r"#\s*metrics-consumer")
_LAUNCHER_ONLY_RE = re.compile(r"#\s*knob:\s*launcher-only")

# argparse dests that are process plumbing on every server, never mirrors
_INFRA_DESTS = {"host", "port"}


def _scoped(display_path: str) -> bool:
    """Registration/argparse/_info checks: launcher servers + fixtures."""
    p = display_path.replace("\\", "/")
    return "launcher/" in p or "fixtures" in p


def _line_has(sf: SourceFile, line: int, rx: re.Pattern) -> bool:
    """The annotation is on the node's line or the preceding comment line
    (same placement contract as inline pragmas)."""
    for ln in (line, line - 1):
        if 0 < ln <= len(sf.lines) and rx.search(sf.lines[ln - 1]):
            if ln == line or sf.lines[ln - 1].strip().startswith("#"):
                return True
    return False


@dataclass
class _Site:
    file: str
    line: int


@dataclass
class WireState:
    """Cross-file accumulator for the AR3xx wire contracts."""

    # AR301
    routes: dict[str, list[tuple[_Site, bool, bool]]] = field(
        default_factory=dict
    )  # path -> [(site, in_scope, external)]
    client_refs: dict[str, list[_Site]] = field(default_factory=dict)
    # AR302
    seams: dict[str, dict[str, _Site]] = field(
        default_factory=dict
    )  # seam -> {module -> first site}
    patterns: list[tuple[str, _Site]] = field(default_factory=list)
    # AR303
    produced_keys: set[str] = field(default_factory=set)
    declared_keys: list[tuple[str, str, _Site]] = field(
        default_factory=list
    )  # (container, key, site) from *_KEYS tuples
    consumer_reads: list[tuple[str, str, _Site]] = field(
        default_factory=list
    )  # (fn qualname, key, site)
    # AR305
    dataclass_fields: set[str] = field(default_factory=set)
    argparse_flags: list[tuple[str, str, _Site]] = field(
        default_factory=list
    )  # (dest, flag, site)
    info_reads: list[tuple[str, _Site]] = field(default_factory=list)

    _files: dict[str, SourceFile] = field(default_factory=dict)

    def _suppressed(self, f: Finding) -> bool:
        sf = self._files.get(f.file)
        return sf.suppressed(f.rule, f.line) if sf else False

    def finalize(self) -> list[Finding]:
        out: list[Finding] = []

        def emit(rule: str, site: _Site, key: str, msg: str) -> None:
            f = Finding(
                rule=rule, file=site.file, line=site.line, key=key, message=msg
            )
            if not self._suppressed(f):
                out.append(f)

        # -- AR301: route pairing -------------------------------------
        if self.routes:  # a client-only sweep cannot judge pairing
            for path, sites in sorted(self.client_refs.items()):
                if path in self.routes:
                    continue
                for site in sites:
                    emit(
                        "AR301",
                        site,
                        path,
                        f"client references endpoint {path!r} but no "
                        "analyzed server registers it — the call can only "
                        "404",
                    )
            for path, regs in sorted(self.routes.items()):
                if path in self.client_refs:
                    continue
                for site, in_scope, external in regs:
                    if not in_scope or external:
                        continue
                    emit(
                        "AR301",
                        site,
                        path,
                        f"endpoint {path!r} is registered but no analyzed "
                        "client references it — dead route (annotate "
                        "`# wire: external` if it is an ops/bench surface)",
                    )

        # -- AR302: fault-seam validity -------------------------------
        if self.seams:  # a plan-only sweep cannot judge patterns
            for pat, site in self.patterns:
                if not any(fnmatch.fnmatch(s, pat) for s in self.seams):
                    emit(
                        "AR302",
                        site,
                        pat,
                        f"fault pattern {pat!r} matches no harvested seam "
                        "— this FaultPoint silently never fires",
                    )
        for seam, mods in sorted(self.seams.items()):
            if len(mods) > 1:
                first = min(mods.values(), key=lambda s: (s.file, s.line))
                emit(
                    "AR302",
                    first,
                    seam,
                    f"seam {seam!r} is fired from {len(mods)} modules "
                    f"({sorted(mods)}) — one fnmatch pattern now perturbs "
                    "two unrelated boundaries; rename one seam",
                )

        # -- AR303: metrics contract (cross-file halves) --------------
        if self.produced_keys:
            for container, key, site in self.declared_keys:
                if key not in self.produced_keys:
                    emit(
                        "AR303",
                        site,
                        f"{container}.{key}",
                        f"{container} declares metric key {key!r} but no "
                        "analyzed producer exports it — the poll reads a "
                        "key that is never there",
                    )
            for fn, key, site in self.consumer_reads:
                if key not in self.produced_keys:
                    emit(
                        "AR303",
                        site,
                        f"{fn}.{key}",
                        f"metrics consumer {fn}() reads key {key!r} but no "
                        "analyzed producer exports it",
                    )

        # -- AR305: config-knob drift ---------------------------------
        if self.dataclass_fields:
            for dest, flag, site in self.argparse_flags:
                if dest in self.dataclass_fields or dest in _INFRA_DESTS:
                    continue
                emit(
                    "AR305",
                    site,
                    dest,
                    f"argparse flag {flag!r} (dest {dest!r}) mirrors no "
                    "config dataclass field — renamed knob? use an "
                    "explicit dest= or annotate `# knob: launcher-only`",
                )
            for name, site in self.info_reads:
                if name not in self.dataclass_fields:
                    emit(
                        "AR305",
                        site,
                        f"info.{name}",
                        f"/info surface reads self.config.{name} but no "
                        "config dataclass declares that field",
                    )

        return out


def _const_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _path_of(value: str) -> str | None:
    """Normalize a literal to an endpoint path (query string stripped)."""
    p = value.split("?", 1)[0]
    return p if _PATH_RE.match(p) else None


def _fstring_paths(node: ast.JoinedStr) -> list[str]:
    """Leading-constant path pieces of an f-string: `f"/kv_recv?xid={x}"`
    -> ["/kv_recv"], `f"http://{addr}/health"` -> ["/health"]."""
    out = []
    for piece in node.values:
        s = _const_str(piece)
        if s and s.startswith("/"):
            p = _path_of(s)
            if p:
                out.append(p)
    return out


class _Harvest(ast.NodeVisitor):
    def __init__(self, sf: SourceFile, state: WireState):
        self.sf = sf
        self.state = state
        self.scoped = _scoped(sf.display)
        self.module = sf.display
        self.stack: list[str] = []
        self.findings: list[Finding] = []
        # nearest enclosing metrics-producer / metrics-consumer function
        self._producer_depth = 0
        self._consumer: str | None = None
        self._info_depth = 0

    def _site(self, node: ast.AST) -> _Site:
        return _Site(self.sf.display, node.lineno)

    # -- class-local collection (AR303 stats drift, AR304) ------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self._check_stats_drift(node)
        self.generic_visit(node)
        self.stack.pop()

    def _check_stats_drift(self, cls: ast.ClassDef) -> None:
        inits: dict[str, set[str]] = {}
        for n in ast.walk(cls):
            if not (isinstance(n, ast.Assign) and len(n.targets) == 1):
                continue
            t = n.targets[0]
            if not (
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
                and t.attr.endswith(_STATS_SUFFIXES)
            ):
                continue
            keys = _dict_keys(n.value)
            if keys is not None:
                inits.setdefault(t.attr, set()).update(keys)
                # the whole dict is exported via `**` splats in the
                # metrics handlers, so its keys count as produced
                self.state.produced_keys.update(keys)
        if not inits:
            return
        for n in ast.walk(cls):
            tgt = None
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    if isinstance(t, ast.Subscript):
                        tgt = t
            if tgt is None:
                continue
            base = tgt.value
            if not (
                isinstance(base, ast.Attribute)
                and isinstance(base.value, ast.Name)
                and base.value.id == "self"
                and base.attr in inits
            ):
                continue
            key = _const_str(tgt.slice)
            if key is not None and key not in inits[base.attr]:
                self.findings.append(
                    Finding(
                        rule="AR303",
                        file=self.sf.display,
                        line=n.lineno,
                        key=f"{cls.name}.{base.attr}[{key}]",
                        message=(
                            f"self.{base.attr}[{key!r}] is mutated but the "
                            "initializer never declares that key — the "
                            "export misses it until first hit (renamed "
                            "metric?)"
                        ),
                    )
                )

    # -- functions: producer/consumer framing, argparse, _info --------

    def _visit_fn(self, node) -> None:
        self.stack.append(node.name)
        name = node.name
        # `_health` is a producer too: the router poll reads version/role
        # off the health body, so the health surface is part of the
        # contract the same way /metrics is
        produces = (
            name == "get_metrics"
            or name == "_health"
            or name.endswith("_metrics")
            or _line_has(self.sf, node.lineno, _METRICS_PRODUCER_RE)
        )
        consumes = _line_has(self.sf, node.lineno, _METRICS_CONSUMER_RE)
        is_info = self.scoped and name == "_info"
        if produces:
            self._producer_depth += 1
        if is_info:
            self._info_depth += 1
        prev_consumer = self._consumer
        if consumes:
            self._consumer = ".".join(self.stack)
        self.generic_visit(node)
        if produces:
            self._producer_depth -= 1
        if is_info:
            self._info_depth -= 1
        self._consumer = prev_consumer
        self.stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # -- assignments: *_ENDPOINT, *_KEYS, dataclass fields ------------

    def visit_Assign(self, node: ast.Assign) -> None:
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            tname = node.targets[0].id
            if tname.endswith("_ENDPOINT"):
                s = _const_str(node.value)
                p = _path_of(s) if s else None
                if p:
                    self.state.client_refs.setdefault(p, []).append(
                        self._site(node)
                    )
            elif tname.endswith("_KEYS") and isinstance(
                node.value, (ast.Tuple, ast.List)
            ):
                for el in node.value.elts:
                    s = _const_str(el)
                    if s is not None:
                        self.state.declared_keys.append(
                            (tname, s, _Site(self.sf.display, el.lineno))
                        )
        self._maybe_record_produced(node)
        self._maybe_statement_producer(node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._maybe_statement_producer(node)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._maybe_record_produced(node)
        self.generic_visit(node)

    def _maybe_statement_producer(self, node) -> None:
        """`# metrics-producer` on an assignment: every dict key inside
        the value is produced — for entry templates that ride inside a
        metrics body without being built in a producer function (the
        router's breaker defaultdict lambda)."""
        if node.value is None or not _line_has(
            self.sf, node.lineno, _METRICS_PRODUCER_RE
        ):
            return
        for n in ast.walk(node.value):
            keys = _dict_keys(n)
            if keys:
                self.state.produced_keys.update(keys)

    def _maybe_record_produced(self, node) -> None:
        """Inside a metrics producer, `out["k"] = ...` produces "k"."""
        if not self._producer_depth:
            return
        targets = node.targets if isinstance(node, ast.Assign) else [node.target]
        for t in targets:
            if isinstance(t, ast.Subscript):
                key = _const_str(t.slice)
                if key is not None:
                    self.state.produced_keys.add(key)

    # -- dict literals inside producers -------------------------------

    def visit_Dict(self, node: ast.Dict) -> None:
        if self._producer_depth:
            for k in node.keys:
                s = _const_str(k) if k is not None else None
                if s is not None:
                    self.state.produced_keys.add(s)
        # FaultPlan.from_json-style embedded plans: {"site": "<pattern>"}
        for k, v in zip(node.keys, node.values):
            if k is not None and _const_str(k) == "site":
                s = _const_str(v)
                if s:
                    self.state.patterns.append((s, _Site(self.sf.display, v.lineno)))
        self.generic_visit(node)

    # -- calls: routes, HTTP refs, seams, FaultPoint, argparse, dict() --

    def visit_Call(self, node: ast.Call) -> None:
        name = call_root(node) or ""
        leaf = name.rsplit(".", 1)[-1]

        if leaf in _ROUTE_ADDERS:
            for a in node.args:
                s = _const_str(a)
                if s and s.startswith("/"):
                    p = _path_of(s)
                    if p:
                        external = _line_has(
                            self.sf, node.lineno, _WIRE_EXTERNAL_RE
                        )
                        self.state.routes.setdefault(p, []).append(
                            (self._site(node), self.scoped, external)
                        )
                    break

        elif leaf in _HTTP_CALLS:
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                s = _const_str(a)
                if s is not None:
                    p = _path_of(s)
                    if p:
                        self.state.client_refs.setdefault(p, []).append(
                            _Site(self.sf.display, a.lineno)
                        )
                elif isinstance(a, ast.JoinedStr):
                    for p in _fstring_paths(a):
                        self.state.client_refs.setdefault(p, []).append(
                            _Site(self.sf.display, a.lineno)
                        )

        if leaf in _SEAM_ENTRIES and node.args:
            s = _const_str(node.args[0])
            if s:
                self.state.seams.setdefault(s, {}).setdefault(
                    self.module, _Site(self.sf.display, node.lineno)
                )

        if leaf == "FaultPoint":
            pat = None
            pnode = None
            if node.args:
                pat = _const_str(node.args[0])
                pnode = node.args[0]
            for kw in node.keywords:
                if kw.arg == "site":
                    pat = _const_str(kw.value)
                    pnode = kw.value
            if pat and pnode is not None:
                self.state.patterns.append(
                    (pat, _Site(self.sf.display, pnode.lineno))
                )

        if leaf == "dict" and self._producer_depth:
            for kw in node.keywords:
                if kw.arg is not None:
                    self.state.produced_keys.add(kw.arg)

        if leaf == "add_argument" and self.scoped and node.args:
            flag = _const_str(node.args[0])
            if (
                flag
                and flag.startswith("--")
                and not _line_has(self.sf, node.lineno, _LAUNCHER_ONLY_RE)
            ):
                dest = flag[2:].replace("-", "_")
                for kw in node.keywords:
                    if kw.arg == "dest":
                        d = _const_str(kw.value)
                        if d:
                            dest = d
                self.state.argparse_flags.append(
                    (dest, flag, self._site(node))
                )

        if self._consumer and leaf == "get" and node.args:
            s = _const_str(node.args[0])
            if s is not None:
                self.state.consumer_reads.append(
                    (self._consumer, s, self._site(node))
                )

        self.generic_visit(node)

    # -- subscripts: consumer reads -----------------------------------

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if self._consumer and isinstance(node.ctx, ast.Load):
            s = _const_str(node.slice)
            if s is not None:
                self.state.consumer_reads.append(
                    (self._consumer, s, self._site(node))
                )
        self.generic_visit(node)

    # -- attribute reads: /info surface -------------------------------

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self._info_depth:
            v = node.value
            if (
                isinstance(v, ast.Attribute)
                and v.attr == "config"
                and isinstance(v.value, ast.Name)
                and v.value.id == "self"
            ):
                self.state.info_reads.append((node.attr, self._site(node)))
        self.generic_visit(node)


def _dict_keys(value: ast.AST) -> set[str] | None:
    """String keys of a `{...}` or `dict(k=...)` initializer literal."""
    if isinstance(value, ast.Dict):
        out = set()
        for k in value.keys:
            s = _const_str(k) if k is not None else None
            if s is not None:
                out.add(s)
        return out
    if (
        isinstance(value, ast.Call)
        and (call_root(value) or "").rsplit(".", 1)[-1] == "dict"
    ):
        return {kw.arg for kw in value.keywords if kw.arg is not None}
    return None


def _dataclass_fields(tree: ast.Module) -> set[str]:
    out: set[str] = set()
    for node in tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        is_dc = False
        for dec in node.decorator_list:
            d = dec.func if isinstance(dec, ast.Call) else dec
            dname = None
            if isinstance(d, ast.Name):
                dname = d.id
            elif isinstance(d, ast.Attribute):
                dname = d.attr
            if dname == "dataclass":
                is_dc = True
        if not is_dc:
            continue
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ):
                out.add(stmt.target.id)
    return out


def _check_registry_staleness(sf: SourceFile) -> list[Finding]:
    """AR304: `_GUARDED_BY["Class.attr"]` where the class exists in this
    module but never touches `self.attr` — a refactor leftover waiving
    AR101 for nothing."""
    registry, lines = _guard_registry(sf.tree)
    if not registry:
        return []
    classes = {
        n.name: n for n in sf.tree.body if isinstance(n, ast.ClassDef)
    }
    attrs: dict[str, set[str]] = {}
    findings: list[Finding] = []
    for key in sorted(registry):
        cls_name, _, attr = key.partition(".")
        cls = classes.get(cls_name)
        if cls is None or not attr:
            continue  # unknown class is AR104's finding
        if cls_name not in attrs:
            got: set[str] = set()
            for n in ast.walk(cls):
                if (
                    isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self"
                ):
                    got.add(n.attr)
            attrs[cls_name] = got
        if attr not in attrs[cls_name]:
            findings.append(
                Finding(
                    rule="AR304",
                    file=sf.display,
                    line=lines.get(key, 1),
                    key=key,
                    message=(
                        f"_GUARDED_BY entry {key!r} names an attribute "
                        f"{cls_name} never touches — stale after a "
                        "refactor; remove the entry"
                    ),
                )
            )
    return findings


def analyze_wire(sf: SourceFile, state: WireState) -> list[Finding]:
    state._files[sf.display] = sf
    state.dataclass_fields |= _dataclass_fields(sf.tree)
    h = _Harvest(sf, state)
    h.visit(sf.tree)
    return h.findings + _check_registry_staleness(sf)
