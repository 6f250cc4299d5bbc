"""Per-file AST rules: lock discipline, metrics hygiene, knob reads.

Each rule returns Violations; `core.filter_allowed` applies the
``# check: allow(rule)`` suppressions afterwards.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import (Source, Violation, dotted, enclosing_functions,
                   str_const)

# ---------------------------------------------------------------------------
# rule: lock-blocking
# ---------------------------------------------------------------------------

# Hot-path modules: their mutexes sit under per-request traffic, so a
# blocking call inside a `with <lock>:` body convoys every concurrent
# request behind one caller's I/O. Namespace RW locks
# (`ns.new_lock(...).write_locked()`) are exempt by construction — they
# are per-object leases that intentionally span I/O.
LOCK_HOT_MODULES = (
    "minio_tpu/object/metacache.py",
    "minio_tpu/object/cache.py",
    "minio_tpu/object/engine.py",
    "minio_tpu/object/multipart.py",
    "minio_tpu/object/sets.py",
    "minio_tpu/object/server_sets.py",
    "minio_tpu/object/background.py",
    "minio_tpu/parallel/scheduler.py",
    "minio_tpu/parallel/pipeline.py",
    "minio_tpu/parallel/bpool.py",
    "minio_tpu/utils/telemetry.py",
    "minio_tpu/s3/trace.py",
    "minio_tpu/distributed/transport.py",
    "minio_tpu/scan/engine.py",
    "minio_tpu/scan/kernels.py",
)

# a with-context whose final name component looks like a mutex
_LOCK_NAME = re.compile(r"(?i)^_?(?:[a-z0-9]+_)*(?:mu|lock|cond|kick)$")

_OS_BANNED = {
    "replace", "rename", "remove", "unlink", "makedirs", "mkdir",
    "rmdir", "listdir", "scandir", "walk", "stat", "utime", "fsync",
    "open", "close",
}
_OS_PATH_BANNED = {"getsize", "getmtime", "getatime", "exists",
                   "isdir", "isfile"}
_BANNED_PREFIXES = ("shutil.", "socket.", "requests.", "urllib.",
                    "subprocess.")
# blocking calls into the object/storage layer — the metacache bug
# class: a quorum metadata read or erasure write while holding the
# journal lock stalls record(), the PUT hot path
_OBJECT_LAYER = {
    "get_object", "put_object", "delete_object", "delete_objects",
    "get_object_info", "object_versions", "list_objects",
    "list_object_versions", "get_bucket_info", "make_bucket",
    "delete_bucket", "write_metadata", "read_metadata",
    "delete_version", "rename_data", "read_file_stream",
    "for_each_disk", "heal_object",
}
# device dispatch — the PR 6 deadlock class: a mesh/jit launch under a
# lock serializes the backend behind the lock's waiters
_DEVICE = {
    "encode_and_hash_batch", "encrypt_encode_and_hash_batch",
    "verify_and_decode_batch", "verify_decode_decrypt_batch",
    "verify_and_recover_batch", "_launch", "mesh_encode_and_hash",
    "mesh_verify_and_decode", "mesh_verify_and_recover", "run_batch",
    "block_until_ready",
}


def _lock_names(with_node: ast.With) -> List[str]:
    names = []
    for item in with_node.items:
        d = dotted(item.context_expr)
        if d and _LOCK_NAME.match(d.split(".")[-1]):
            names.append(d)
    return names


def _banned_of_call(call: ast.Call) -> Optional[str]:
    """Description of the banned operation this call performs, else
    None (the single home of the banned-call table)."""
    d = dotted(call.func)
    if d == "time.sleep":
        return "time.sleep"
    root, _, rest = d.partition(".")
    if root == "os" and rest in _OS_BANNED:
        return f"os.{rest} (disk I/O)"
    if d.startswith("os.path.") and d.split(".")[-1] in _OS_PATH_BANNED:
        return f"{d} (disk stat)"
    if d.startswith(_BANNED_PREFIXES):
        return f"{d} (I/O)"
    if d in ("json.dump", "json.load"):
        return f"{d} (file I/O)"
    if isinstance(call.func, ast.Name) and call.func.id == "open":
        return "open() (disk I/O)"
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        recv = dotted(call.func.value)
        if attr == "result":
            return "future .result()"
        if attr == "wait" and not (
                recv and _LOCK_NAME.match(recv.split(".")[-1])):
            # cond.wait releases the lock it guards — fine; any OTHER
            # .wait (events, futures) blocks while holding
            return f"{recv or '?'}.wait()"
        if attr in _OBJECT_LAYER:
            return f"object/storage-layer call .{attr}()"
        if attr in _DEVICE:
            return f"device dispatch .{attr}()"
    return None


def _helper_banned_map(src: Source) -> Dict[str, str]:
    """method/function name -> banned-op description, for every def in
    this file whose DIRECT body performs a banned call. One level of
    indirection: `with self._mu: self._write_meta(...)` is the same
    hazard as inlining the open() itself."""
    out: Dict[str, str] = {}
    for node in ast.walk(src.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack: List[ast.AST] = list(node.body)
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue                    # nested defs run later
            if isinstance(sub, ast.Call):
                what = _banned_of_call(sub)
                if what is not None:
                    out.setdefault(node.name, what)
                    stack.clear()
                    continue
            stack.extend(ast.iter_child_nodes(sub))
    return out


def _scan_lock_body(src: Source, lock: str, body: List[ast.stmt],
                    helpers: Dict[str, str],
                    out: List[Violation]) -> None:
    def flag(node: ast.AST, what: str) -> None:
        out.append(Violation(
            "lock-blocking", src.rel, node.lineno,
            f"{what} inside `with {lock}:` — blocking work under a "
            "hot lock convoys every waiter; move it outside the "
            "critical section"))

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue            # runs later, not under this hold
            if isinstance(child, ast.Call):
                _check_call(child)
            visit(child)

    def _check_call(call: ast.Call) -> None:
        what = _banned_of_call(call)
        if what is not None:
            flag(call, what)
            return
        # one level of same-file helper indirection
        if isinstance(call.func, ast.Attribute) and \
                dotted(call.func.value) == "self":
            hb = helpers.get(call.func.attr)
            if hb is not None:
                flag(call, f"self.{call.func.attr}() which performs "
                     f"{hb}")

    for stmt in body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue                    # defined under the lock, runs later
        visit(stmt)


def check_lock_blocking(sources: List[Source]) -> List[Violation]:
    out: List[Violation] = []
    hot = set(LOCK_HOT_MODULES)
    for src in sources:
        if src.rel not in hot:
            continue
        helpers = _helper_banned_map(src)
        # manual lock management sidesteps the with-body scan entirely
        # (`x.acquire(); try: ... finally: x.release()` holds the lock
        # across anything) — flag the spelling itself; a deliberate
        # non-blocking try-acquire argues its suppression inline
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "acquire":
                recv = dotted(node.func.value)
                if recv and _LOCK_NAME.match(recv.split(".")[-1]):
                    out.append(Violation(
                        "lock-blocking", src.rel, node.lineno,
                        f"manual {recv}.acquire() — the with-body lint "
                        "cannot see what runs under this hold; use "
                        "`with` or argue a suppression"))
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.With):
                continue
            locks = _lock_names(node)
            if not locks:
                continue
            vs: List[Violation] = []
            _scan_lock_body(src, locks[0], node.body, helpers, vs)
            # suppression on the `with` line (or directly above it)
            # covers the whole body; is_allowed already looks one line
            # up, so no extra offset here
            if src.is_allowed("lock-blocking", node.lineno):
                continue
            out.extend(vs)
    return out


# ---------------------------------------------------------------------------
# rule: metrics-hygiene
# ---------------------------------------------------------------------------

_GETTERS = {"counter", "gauge", "histogram"}
# function names allowed to resolve metric families: init scope and
# the documented resolver conventions (collectors run at exposition
# time; *_metrics/*_counter helpers are called once and cached by
# their callers; `global`-memoized resolvers are the one-time pattern)
_SCOPE_OK = re.compile(r"^(?:__init__|__new__|_?metrics|_?collect\w*|"
                       r"_?register\w*)$")
_SCOPE_OK_SUFFIX = ("_metrics", "_counter", "_gauge", "_histogram",
                    "_families")


def _has_global(fn: ast.AST) -> bool:
    return any(isinstance(n, ast.Global) for n in ast.walk(fn))


def check_metrics_hygiene(sources: List[Source]) -> List[Violation]:
    out: List[Violation] = []
    # family name -> (kind, src.rel, line, help)
    registry: Dict[str, Tuple[str, str, int, Optional[str]]] = {}
    # family name -> {frozenset(labels): (rel, line)}
    labels: Dict[str, Dict[frozenset, Tuple[str, int]]] = {}

    for src in sources:
        encl = enclosing_functions(src.tree)
        # var name (scoped by enclosing fn or None) -> family name
        var_family: Dict[Tuple[Optional[ast.AST], str], str] = {}

        def record_labels(fam: str, call: ast.Call) -> None:
            lbls = frozenset(k.arg for k in call.keywords
                             if k.arg is not None)
            labels.setdefault(fam, {}).setdefault(
                lbls, (src.rel, call.lineno))

        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if not isinstance(func, ast.Attribute):
                continue
            if func.attr in _GETTERS:
                name = str_const(node.args[0]) if node.args else None
                if name is None or not name.startswith("minio_tpu_"):
                    continue
                line = node.lineno
                kind = func.attr
                help_ = str_const(node.args[1]) \
                    if len(node.args) > 1 else None
                if kind == "counter" and not name.endswith("_total"):
                    out.append(Violation(
                        "metrics-hygiene", src.rel, line,
                        f"Counter {name!r} must end in `_total` "
                        "(Prometheus counter naming)"))
                if kind != "counter" and name.endswith("_total"):
                    out.append(Violation(
                        "metrics-hygiene", src.rel, line,
                        f"{kind} {name!r} ends in `_total` but is not "
                        "a Counter"))
                seen = registry.get(name)
                if seen is None:
                    registry[name] = (kind, src.rel, line, help_)
                else:
                    if seen[0] != kind:
                        out.append(Violation(
                            "metrics-hygiene", src.rel, line,
                            f"metric {name!r} registered as {kind} "
                            f"here but {seen[0]} at {seen[1]}:"
                            f"{seen[2]} — one family, one kind"))
                    elif (help_ and seen[3] and help_ != seen[3]):
                        out.append(Violation(
                            "metrics-hygiene", src.rel, line,
                            f"metric {name!r} registered with a "
                            f"different help string than {seen[1]}:"
                            f"{seen[2]} — two subsystems think they "
                            "own this name"))
                # scope discipline: resolving a family takes the
                # registry mutex — never per call on a hot path
                fn = encl.get(node)
                if fn is not None:
                    fname = fn.name
                    ok = (_SCOPE_OK.match(fname)
                          or fname.endswith(_SCOPE_OK_SUFFIX)
                          or _has_global(fn))
                    if not ok:
                        out.append(Violation(
                            "metrics-hygiene", src.rel, line,
                            f"metric family {name!r} resolved inside "
                            f"{fname}() — resolve at init scope (or a "
                            "*_metrics/_collect*/global-memoized "
                            "resolver); registry lookups take the "
                            "global metrics mutex"))
                # direct chain: REGISTRY.counter("n").inc(labels...)
                # handled below via parent scan
            elif func.attr in ("inc", "set", "observe"):
                recv = func.value
                fam: Optional[str] = None
                if isinstance(recv, ast.Call) and \
                        isinstance(recv.func, ast.Attribute) and \
                        recv.func.attr in _GETTERS and recv.args:
                    fam = str_const(recv.args[0])
                elif isinstance(recv, ast.Name):
                    fn = encl.get(node)
                    fam = var_family.get((fn, recv.id)) or \
                        var_family.get((None, recv.id))
                if fam:
                    record_labels(fam, node)

        # second pass: var assignments from registry getters (module
        # and function scope), then re-scan inc/set/observe on them
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute) \
                    and node.value.func.attr in _GETTERS \
                    and node.value.args:
                fam = str_const(node.value.args[0])
                if fam and fam.startswith("minio_tpu_"):
                    var_family[(encl.get(node), node.targets[0].id)] = fam
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("inc", "set", "observe") and \
                    isinstance(node.func.value, ast.Name):
                fn = encl.get(node)
                fam = var_family.get((fn, node.func.value.id)) or \
                    var_family.get((None, node.func.value.id))
                if fam:
                    record_labels(fam, node)

    # label-set consistency per family across the whole tree
    for fam, sets_ in labels.items():
        if len(sets_) > 1:
            items = sorted(sets_.items(), key=lambda kv: kv[1])
            first_lbls, (rel0, ln0) = items[0]
            for lbls, (rel, ln) in items[1:]:
                out.append(Violation(
                    "metrics-hygiene", rel, ln,
                    f"metric {fam!r} used with labels "
                    f"{sorted(lbls) or '(none)'} here but "
                    f"{sorted(first_lbls) or '(none)'} at {rel0}:{ln0} "
                    "— label sets must be consistent per family"))
    return out


# ---------------------------------------------------------------------------
# rule: metrics-hygiene / label cardinality
# ---------------------------------------------------------------------------

# Hot-path modules whose metric label VALUES must stay bounded: a
# per-request metric labelled by a raw bucket/object/key name grows one
# series per distinct name — unbounded registry memory, an exposition
# whose size scales with the namespace, and a Prometheus server that
# falls over on the scrape. Bounded labels (verb, api, reason, stage,
# target, node, kind, source, consumer, tier, pool, loop, path-as-enum)
# come from small closed vocabularies and stay clean.
CARDINALITY_HOT_MODULES = LOCK_HOT_MODULES + (
    "minio_tpu/s3/handlers.py",
    "minio_tpu/s3/edge/dispatch.py",
    "minio_tpu/s3/edge/server.py",
    "minio_tpu/s3/edge/admission.py",
    "minio_tpu/s3/qos.py",
    "minio_tpu/object/codec.py",
    "minio_tpu/object/healing.py",
)
# label KEYS that name request-derived identifiers: always unbounded,
# regardless of what expression feeds them
_UNBOUNDED_LABEL_KEYS = {
    "bucket", "object", "key", "obj", "etag", "version_id",
    "upload_id", "prefix", "trace_id", "request_id", "caller",
}
# non-constant label VALUE expressions whose terminal name screams
# request-derived (counter.inc(verb=bucket) is the same bug with a
# clean key)
_UNBOUNDED_VALUE_NAMES = _UNBOUNDED_LABEL_KEYS | {"path", "name"}

_METRIC_METHODS = {"inc", "set", "observe"}


def check_label_cardinality(sources: List[Source]) -> List[Violation]:
    """metrics-hygiene sub-rule: in hot-path modules, metric label
    values must come from bounded vocabularies — raw bucket/object/key
    names (or any request-derived value) as a label value fails."""
    out: List[Violation] = []
    hot = set(CARDINALITY_HOT_MODULES)
    for src in sources:
        if src.rel not in hot:
            continue
        encl = enclosing_functions(src.tree)
        # getter aliases (`g = telemetry.REGISTRY.gauge; g("n").set(…)`)
        # — the attribute-only scan's blind spot; ONE scanner shared
        # with the metrics table so the lint and the README can never
        # disagree on which registration sites exist
        from .metricstable import getter_aliases
        aliases = getter_aliases(src.tree)
        # var name (scoped like the hygiene rule) -> metric family
        var_family: Dict[Tuple[Optional[ast.AST], str], str] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute) \
                    and node.value.func.attr in _GETTERS \
                    and node.value.args:
                fam = str_const(node.value.args[0])
                if fam and fam.startswith("minio_"):
                    var_family[(encl.get(node), node.targets[0].id)] = fam
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_METHODS):
                continue
            recv = node.func.value
            fam: Optional[str] = None
            if isinstance(recv, ast.Call) and recv.args and (
                    (isinstance(recv.func, ast.Attribute)
                     and recv.func.attr in _GETTERS)
                    or (isinstance(recv.func, ast.Name)
                        and recv.func.id in aliases)):
                fam = str_const(recv.args[0])
            elif isinstance(recv, ast.Name):
                fn = encl.get(node)
                fam = var_family.get((fn, recv.id)) or \
                    var_family.get((None, recv.id))
            if not fam:
                continue
            for kw in node.keywords:
                if kw.arg is None:
                    continue
                if kw.arg in _UNBOUNDED_LABEL_KEYS:
                    out.append(Violation(
                        "metrics-hygiene", src.rel, node.lineno,
                        f"metric {fam!r} labelled by request-derived "
                        f"{kw.arg!r} — one series per distinct "
                        "bucket/object/key is unbounded cardinality; "
                        "aggregate or drop the label"))
                    continue
                if isinstance(kw.value, ast.Constant):
                    continue            # literal value: bounded
                d = dotted(kw.value)
                if d and d.split(".")[-1] in _UNBOUNDED_VALUE_NAMES:
                    out.append(Violation(
                        "metrics-hygiene", src.rel, node.lineno,
                        f"metric {fam!r} label {kw.arg!r} fed by "
                        f"request-derived value `{d}` — unbounded "
                        "cardinality in a hot-path module"))
    return out


# ---------------------------------------------------------------------------
# rule: knob-env
# ---------------------------------------------------------------------------

_KNOB_GETTERS = {"get_str", "get_int", "get_float", "get_bool",
                 "get_raw", "is_set", "get"}


def check_knob_env(sources: List[Source],
                   registered: Set[str]) -> List[Violation]:
    """All MINIO_TPU_* environment access goes through utils/knobs.py;
    knob getter calls must name a registered knob."""
    out: List[Violation] = []
    for src in sources:
        is_knobs = src.rel.endswith("utils/knobs.py")
        for node in ast.walk(src.tree):
            # os.environ.get("MINIO_TPU_...") / os.getenv(...)
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d in ("os.environ.get", "os.getenv", "os.environ.pop",
                         "os.environ.setdefault") and not is_knobs:
                    name = str_const(node.args[0]) if node.args else None
                    if name and name.startswith("MINIO_TPU_"):
                        out.append(Violation(
                            "knob-env", src.rel, node.lineno,
                            f"raw environ access for {name!r} — go "
                            "through minio_tpu/utils/knobs.py "
                            "(declare the knob there)"))
                elif d.split(".")[-1] in _KNOB_GETTERS and \
                        d.split(".")[0] in ("knobs",) and node.args:
                    name = str_const(node.args[0])
                    if name and name not in registered:
                        out.append(Violation(
                            "knob-env", src.rel, node.lineno,
                            f"knobs getter names unregistered knob "
                            f"{name!r} — declare it in utils/knobs.py"))
            # os.environ["MINIO_TPU_..."] (read or write)
            elif isinstance(node, ast.Subscript) and not is_knobs:
                if dotted(node.value) == "os.environ":
                    name = str_const(node.slice)
                    if name and name.startswith("MINIO_TPU_"):
                        out.append(Violation(
                            "knob-env", src.rel, node.lineno,
                            f"raw os.environ[{name!r}] — go through "
                            "minio_tpu/utils/knobs.py"))
            # "MINIO_TPU_X" in os.environ
            elif isinstance(node, ast.Compare) and not is_knobs:
                if len(node.comparators) == 1 and \
                        dotted(node.comparators[0]) == "os.environ":
                    name = str_const(node.left)
                    if name and name.startswith("MINIO_TPU_"):
                        out.append(Violation(
                            "knob-env", src.rel, node.lineno,
                            f"raw `{name} in os.environ` — use "
                            "knobs.is_set()"))
    return out


# ---------------------------------------------------------------------------
# rule: admission
# ---------------------------------------------------------------------------

# The ONE module allowed to make shed decisions: every SlowDown
# construction and every requests_shed_total reference lives here, so
# the edge, the threaded frontend and the handlers cannot each grow a
# private shed path that diverges in counters or Retry-After/close
# semantics (migrating the handlers' original shed window into the
# controller is what proved this rule fires).
ADMISSION_MODULE = "minio_tpu/s3/edge/admission.py"
SHED_COUNTER = "minio_tpu_requests_shed_total"

# The refusal probes of the tenant QoS plane: TokenBucket.try_take /
# TokenBucket.peek answer "would this request fit the budget RIGHT
# NOW" — the only legitimate consumers are the AdmissionController and
# the QoS plane it consults (plus the bucket implementation itself).
# A try_take/peek anywhere else is a private shed path in the making:
# the caller has a refusal in hand and nowhere to route it but its own
# 503. (Blocking `take()` stays free — pacing is not a refusal.)
QOS_PROBE_MODULES = (
    ADMISSION_MODULE,
    "minio_tpu/s3/qos.py",
    "minio_tpu/utils/bandwidth.py",
)
_QOS_PROBE_ATTRS = ("try_take", "peek")


def check_admission(sources: List[Source]) -> List[Violation]:
    out: List[Violation] = []
    probe_free = set(QOS_PROBE_MODULES)
    for src in sources:
        if src.rel not in probe_free:
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Call) and node.args and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _QOS_PROBE_ATTRS:
                    out.append(Violation(
                        "admission", src.rel, node.lineno,
                        f".{node.func.attr}() budget probe outside the "
                        "admission/QoS plane — a tenant-budget refusal "
                        "must shed through "
                        f"{ADMISSION_MODULE}, never a private 503 path"))
        if src.rel == ADMISSION_MODULE:
            continue
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) and node.args and \
                    dotted(node.func).split(".")[-1] == "S3Error" and \
                    str_const(node.args[0]) == "SlowDown":
                out.append(Violation(
                    "admission", src.rel, node.lineno,
                    "S3Error(\"SlowDown\") constructed outside the "
                    "AdmissionController — every shed decision must go "
                    f"through {ADMISSION_MODULE}"))
            elif isinstance(node, ast.Constant) and \
                    node.value == SHED_COUNTER:
                out.append(Violation(
                    "admission", src.rel, node.lineno,
                    f"{SHED_COUNTER} referenced outside the "
                    "AdmissionController — shed accounting has ONE "
                    f"home, {ADMISSION_MODULE}"))
    return out


# ---------------------------------------------------------------------------
# rule: deadline
# ---------------------------------------------------------------------------

# Hot-path fan-out modules: every shard fan-out / internode wait here
# sits under per-request traffic, so a bare unbounded `.result()` (or
# raw socket `.recv`) lets ONE gray drive or peer hold a whole
# GET/PUT — the exact tail-latency hole the hedged reader and the
# quorum-ack lane exist to close. A wait is clean when it carries a
# timeout argument, rides the hedged reader / for_each_disk_quorum, or
# argues its bound inline via `# check: allow(deadline) <reason>`.
DEADLINE_HOT_MODULES = (
    "minio_tpu/object/engine.py",
    "minio_tpu/object/metadata.py",
    "minio_tpu/object/multipart.py",
    "minio_tpu/object/healing.py",
    "minio_tpu/distributed/transport.py",
    "minio_tpu/distributed/storage_rpc.py",
    "minio_tpu/distributed/peer_rpc.py",
)

_UNBOUNDED_WAIT_ATTRS = {"recv", "recv_into"}

# streamed-RPC body reads: a peer that goes silent after sending its
# headers parks a bare http resp.read()/readline() forever — the
# connection timeout only covers the DIAL. Every such read in a hot
# module must sit in a function that arms a per-read socket deadline
# (settimeout / _arm_read_deadline) or builds the connection with an
# explicit timeout (whole-body reads under the request window).
_STREAM_READ_ATTRS = {"read", "readline"}


def _read_deadline_armed(fn) -> bool:
    if fn is None:
        return False
    for c in ast.walk(fn):
        if not isinstance(c, ast.Call):
            continue
        tail = dotted(c.func).rsplit(".", 1)[-1]
        if tail in ("settimeout", "_arm_read_deadline"):
            return True
        if tail == "HTTPConnection" and any(
                kw.arg == "timeout" for kw in c.keywords):
            return True
    return False


def check_deadline(sources: List[Source]) -> List[Violation]:
    out: List[Violation] = []
    hot = set(DEADLINE_HOT_MODULES)
    for src in sources:
        if src.rel not in hot:
            continue
        enclosing = enclosing_functions(src.tree)
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            attr = node.func.attr
            if attr in _STREAM_READ_ATTRS:
                recv = dotted(node.func.value)
                if recv.endswith("resp") and \
                        not _read_deadline_armed(enclosing.get(node)):
                    out.append(Violation(
                        "deadline", src.rel, node.lineno,
                        f"{recv}.{attr}() without a read deadline — a "
                        "peer going silent mid-stream parks this "
                        "forever; arm the socket (settimeout / "
                        "_arm_read_deadline) or bound the connection, "
                        "or argue the bound inline"))
                continue
            if attr == "result":
                bounded = bool(node.args) or any(
                    kw.arg == "timeout" for kw in node.keywords)
                if not bounded:
                    out.append(Violation(
                        "deadline", src.rel, node.lineno,
                        "bare unbounded future .result() on a "
                        "hot-path fan-out — pass a timeout, ride the "
                        "hedged reader / for_each_disk_quorum lane, "
                        "or argue the bound inline"))
            elif attr in _UNBOUNDED_WAIT_ATTRS:
                out.append(Violation(
                    "deadline", src.rel, node.lineno,
                    f"raw socket .{attr}() on a hot-path module — "
                    "set a socket timeout and argue the bound inline"))
    return out
