"""Repo-level static lint: flag hygiene + lock discipline.

Two checks, both exit-nonzero-and-name-the-line (the
check_stat_coverage.py convention), run from `make check`:

**Flags.**  Every ``FLAGS_*`` name READ anywhere in ``paddle_tpu/``
(``get_flag('FLAGS_x')``, ``get_flags([...])``, ``os.environ`` access)
must be declared in ``fluid/flags.py``'s ``_DEFAULTS`` — an undeclared
read silently returns the fallback default forever, the classic
mis-spelled-knob production bug.  And the inverse: a flag declared but
never read anywhere in the repo is dead surface (a rename that left
the old declaration behind) and is reported too.

**Locks.**  Module-level mutable registries (dicts/lists/sets assigned
at module scope) in the long-running service modules must only be
mutated under that module's module-level lock: a registry append
outside ``with _lock:`` is exactly the torn-/statusz-read bug this
repo's report trails exist to avoid.  ``monitor.py`` is the documented
exemption — its registries are GIL-disciplined by design (stats-grade
relaxed counters, see its module docstring) and carry no lock at all;
the lint asserts that stays true (adding a lock there without wiring
every site would be worse than none).

AST-based: no imports of the checked modules, so it runs in CI without
jax.  A line may opt out with a trailing ``# staticcheck: unlocked``
comment naming its reason — mutations that are init-time-only or
publish-by-rebind patterns.
"""

import ast
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, 'paddle_tpu')
FLAGS_FILE = os.path.join(PKG, 'fluid', 'flags.py')

# modules whose module-level registries must be lock-disciplined
LOCK_MODULES = [
    'paddle_tpu/fluid/serving.py',
    'paddle_tpu/fluid/health.py',
    'paddle_tpu/fluid/progcheck.py',
    'paddle_tpu/fluid/comms_plan.py',
    'paddle_tpu/fluid/elastic.py',
    'paddle_tpu/fluid/faultinject.py',
    'paddle_tpu/fluid/supervisor.py',
    'paddle_tpu/parallel/plan.py',
    'paddle_tpu/fluid/timeseries.py',
]
# documented GIL-discipline exemption: registries with NO lock at all
# (the lint fails if a lock ever appears there half-wired)
GIL_MODULES = ['paddle_tpu/fluid/monitor.py']

MUTATING_METHODS = {
    'append', 'add', 'pop', 'popitem', 'clear', 'update', 'remove',
    'discard', 'extend', 'insert', 'setdefault', '__setitem__',
}

WAIVER = re.compile(r'#\s*staticcheck:\s*unlocked')


def _py_files(root):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != '__pycache__']
        for f in filenames:
            if f.endswith('.py'):
                yield os.path.join(dirpath, f)


# ------------------------------------------------------------- flags lint

_READ_PATTERNS = (
    re.compile(r"get_flag\(\s*['\"](FLAGS_\w+)"),
    re.compile(r"environ(?:\.get)?\(\s*['\"](FLAGS_\w+)"),
    re.compile(r"environ\[\s*['\"](FLAGS_\w+)"),
    re.compile(r"getenv\(\s*['\"](FLAGS_\w+)"),
)
_GET_FLAGS_LIST = re.compile(r"get_flags\(\s*(\[[^\]]*\]|['\"]FLAGS_\w+['\"])",
                             re.S)
_FLAG_NAME = re.compile(r"FLAGS_\w+")


def declared_flags():
    """(declared flag set, compat-only flag set) from flags.py's AST."""
    with open(FLAGS_FILE) as f:
        tree = ast.parse(f.read(), FLAGS_FILE)
    declared = compat = None
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        names = [getattr(t, 'id', None) for t in node.targets]
        if '_DEFAULTS' in names:
            declared = set(
                k.value for k in node.value.keys
                if isinstance(k, ast.Constant) and
                str(k.value).startswith('FLAGS_'))
        if 'V16_COMPAT_ONLY' in names:
            compat = set(
                e.value for e in node.value.elts
                if isinstance(e, ast.Constant))
    if declared is None:
        raise AssertionError('no _DEFAULTS dict found in flags.py')
    return declared, compat or set()


def flag_reads(paths):
    """{flag: [(file, lineno), ...]} over explicit read sites."""
    reads = {}

    def note(name, path, lineno):
        reads.setdefault(name, []).append(
            (os.path.relpath(path, ROOT), lineno))

    for path in paths:
        with open(path) as f:
            src = f.read()
        for pat in _READ_PATTERNS:
            for m in pat.finditer(src):
                note(m.group(1), path, src[:m.start()].count('\n') + 1)
        for m in _GET_FLAGS_LIST.finditer(src):
            for name in _FLAG_NAME.findall(m.group(1)):
                note(name, path, src[:m.start()].count('\n') + 1)
    return reads


def check_flags(errors):
    declared, compat = declared_flags()
    pkg_reads = flag_reads(_py_files(PKG))
    for name in sorted(pkg_reads):
        if name not in declared:
            f, ln = pkg_reads[name][0]
            errors.append(
                'FLAG UNDECLARED  %s read at %s:%d but not declared '
                'in fluid/flags.py _DEFAULTS (a typo here silently '
                'reads the fallback default forever)' % (name, f, ln))
    # reads anywhere in the repo count against dead-declaration
    # (tools / tests legitimately read runtime flags)
    all_reads = dict(pkg_reads)
    extra = [p for p in _py_files(ROOT)
             if not p.startswith(PKG + os.sep)]
    for name, sites in flag_reads(extra).items():
        all_reads.setdefault(name, []).extend(sites)
    for name in sorted(declared):
        if name not in all_reads and name not in compat:
            errors.append(
                'FLAG NEVER READ  %s is declared in fluid/flags.py '
                'but no code reads it (dead knob or renamed read '
                'site; v1.6 compat-only knobs belong in '
                'V16_COMPAT_ONLY)' % name)
    # pallas kernel knobs must gate dispatch inside the package — a
    # FLAGS_pallas_* read only by tests would pass the generic
    # dead-knob check above while the kernel library silently never
    # consults it (a dense fallback masquerading as a fused win)
    for name in sorted(declared):
        if name.startswith('FLAGS_pallas_') and name not in pkg_reads:
            errors.append(
                'FLAG PALLAS UNWIRED  %s is declared but no '
                'paddle_tpu/ code reads it — pallas dispatch knobs '
                'must be consulted by the kernel library itself, not '
                'only by tests or tools' % name)
    for name in sorted(compat):
        if name in pkg_reads:
            f, ln = pkg_reads[name][0]
            errors.append(
                'FLAG COMPAT VIOLATION  %s is declared compat-only '
                'but is read at %s:%d — move it out of '
                'V16_COMPAT_ONLY' % (name, f, ln))
    return len(declared), sum(len(v) for v in pkg_reads.values())


# -------------------------------------------------------------- lock lint

def _module_registries_and_locks(tree):
    regs, locks = set(), set()
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        for t in node.targets:
            name = getattr(t, 'id', None)
            if name is None or name.startswith('__'):
                continue   # __all__ etc. are not runtime registries
            v = node.value
            if isinstance(v, (ast.Dict, ast.List, ast.Set)) or (
                    isinstance(v, ast.Call) and
                    getattr(v.func, 'id', None) in ('dict', 'list',
                                                    'set')):
                regs.add(name)
            if isinstance(v, ast.Call):
                attr = getattr(v.func, 'attr', None)
                if attr in ('Lock', 'RLock'):
                    locks.add(name)
    return regs, locks


class _LockWalker(ast.NodeVisitor):
    """Flags mutations of module registries outside `with <lock>:`."""

    def __init__(self, regs, locks, src_lines):
        self.regs = regs
        self.locks = locks
        self.src_lines = src_lines
        self.depth = 0        # locks held (lexically)
        self.func_depth = 0
        self.found = []

    def _waived(self, node):
        line = self.src_lines[node.lineno - 1] \
            if node.lineno - 1 < len(self.src_lines) else ''
        return WAIVER.search(line) is not None

    def _is_reg(self, expr):
        return isinstance(expr, ast.Name) and expr.id in self.regs

    def _flag(self, node, what):
        if self.func_depth == 0:
            return   # import-time initialization is single-threaded
        if self.depth == 0 and not self._waived(node):
            self.found.append((node.lineno, what))

    def visit_With(self, node):
        held = any(
            isinstance(item.context_expr, ast.Call) and
            isinstance(item.context_expr.func, ast.Name) and
            item.context_expr.func.id in self.locks
            for item in node.items) or any(
            isinstance(item.context_expr, ast.Name) and
            item.context_expr.id in self.locks
            for item in node.items)
        if held:
            self.depth += 1
        self.generic_visit(node)
        if held:
            self.depth -= 1

    def visit_FunctionDef(self, node):
        self.func_depth += 1
        self.generic_visit(node)
        self.func_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute) and self._is_reg(f.value) and \
                f.attr in MUTATING_METHODS:
            self._flag(node, '%s.%s(...)' % (f.value.id, f.attr))
        self.generic_visit(node)

    def visit_Assign(self, node):
        for t in node.targets:
            if isinstance(t, ast.Subscript) and self._is_reg(t.value):
                self._flag(node, '%s[...] = ...' % t.value.id)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        t = node.target
        if isinstance(t, ast.Subscript) and self._is_reg(t.value):
            self._flag(node, '%s[...] op= ...' % t.value.id)
        self.generic_visit(node)

    def visit_Delete(self, node):
        for t in node.targets:
            if isinstance(t, ast.Subscript) and self._is_reg(t.value):
                self._flag(node, 'del %s[...]' % t.value.id)
        self.generic_visit(node)


def check_locks(errors):
    checked = 0
    for rel in LOCK_MODULES + GIL_MODULES:
        path = os.path.join(ROOT, rel)
        with open(path) as f:
            src = f.read()
        tree = ast.parse(src, path)
        regs, locks = _module_registries_and_locks(tree)
        if rel in GIL_MODULES:
            if locks:
                errors.append(
                    'LOCK DISCIPLINE  %s declares a module lock %s '
                    'but is the documented GIL-discipline module — '
                    'either wire every registry site through it or '
                    'remove it' % (rel, sorted(locks)))
            continue
        if regs and not locks:
            errors.append(
                'LOCK DISCIPLINE  %s has module registries %s but no '
                'module-level threading.Lock' % (rel, sorted(regs)))
            continue
        walker = _LockWalker(regs, locks, src.splitlines())
        walker.visit(tree)
        checked += len(regs)
        for lineno, what in walker.found:
            errors.append(
                'LOCK DISCIPLINE  %s:%d mutates a module registry '
                'outside its lock: %s (wrap in `with %s:` or waive '
                'with `# staticcheck: unlocked`)'
                % (rel, lineno, what, sorted(locks)[0]))
    return checked


def main():
    errors = []
    n_declared, n_reads = check_flags(errors)
    n_regs = check_locks(errors)
    if errors:
        for e in errors:
            print(e)
        print('staticcheck: %d problem(s)' % len(errors))
        return 1
    print('staticcheck: %d flags declared / %d read sites consistent; '
          '%d lock-disciplined registries clean' %
          (n_declared, n_reads, n_regs))
    return 0


if __name__ == '__main__':
    sys.exit(main())
