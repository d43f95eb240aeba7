"""The import graph of ``paddle_tpu/fluid``, read from the AST with
nothing imported.

Two things no other test holds.  A lazy ``from . import X`` inside a
``try: ... except Exception`` fails in silence when X is gone (the
status plane's sections and the step boundary's hooks are written so):
every relative import of every module has to name something that
exists.  And the base of the telemetry (flags, the counter registry,
the tracer, the sampler, fault injection) imports nothing of the
package above itself, so anything may import it without a cycle.
ROADMAP.md's design debt D11 lists the cycles and upward arrows that
remain above the base; ``python tests/test_layering.py`` prints them."""

import ast
import os

import pytest

FLUID = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'paddle_tpu', 'fluid')
MODULES = sorted(f[:-3] for f in os.listdir(FLUID) if f.endswith('.py'))
# what everything else may import without a cycle
BASE = ('flags', 'core', 'monitor', 'trace', 'timeseries',
        'faultinject')


def _exists(pkg_dir, name):
    return os.path.isfile(os.path.join(pkg_dir, name + '.py')) or \
        os.path.isfile(os.path.join(pkg_dir, name, '__init__.py'))


def _binds(pkg_dir, name):
    """Whether the package's ``__init__`` binds `name` itself."""
    with open(os.path.join(pkg_dir, '__init__.py')) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            if any((a.asname or a.name.split('.')[0]) == name
                   for a in node.names):
                return True
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            if node.name == name:
                return True
        elif isinstance(node, ast.Name) and node.id == name and \
                isinstance(node.ctx, ast.Store):
            return True
    return False


def relative_imports(module):
    """[(lineno, level, dotted module or '', imported name)] of every
    relative import of ``fluid/<module>.py``, at any depth."""
    with open(os.path.join(FLUID, module + '.py')) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level:
            for a in node.names:
                out.append((node.lineno, node.level,
                            node.module or '', a.name))
    return out


def fluid_targets(module):
    """{fluid module imported: first line} for one module: the arrows
    of the package's own graph (``from . import X``, ``from .X import
    ...``; a subpackage counts by its first name)."""
    out = {}
    for lineno, level, dotted, name in relative_imports(module):
        if level != 1:
            continue
        target = dotted.split('.')[0] if dotted else name
        if _exists(FLUID, target):
            out.setdefault(target, lineno)
    return out


def fluid_import_graph():
    """The package's graph below ``__init__`` (which imports the lot
    and is imported by none)."""
    return {m: fluid_targets(m) for m in MODULES if m != '__init__'}


def cycles(graph):
    """The strongly connected components of more than one module
    (Tarjan), each sorted, the largest first."""
    index, low, stack, on, comps = {}, {}, [], set(), []

    def visit(v):
        index[v] = low[v] = len(index)
        stack.append(v)
        on.add(v)
        for w in graph.get(v, ()):
            if w not in graph:
                continue
            if w not in index:
                visit(w)
                low[v] = min(low[v], low[w])
            elif w in on:
                low[v] = min(low[v], index[w])
        if low[v] == index[v]:
            comp = []
            while True:
                w = stack.pop()
                on.discard(w)
                comp.append(w)
                if w == v:
                    break
            comps.append(sorted(comp))

    for v in sorted(graph):
        if v not in index:
            visit(v)
    return sorted((c for c in comps if len(c) > 1),
                  key=lambda c: (-len(c), c))


def _resolves(level, dotted, name):
    """Whether ``from <level dots><dotted> import <name>`` names
    something that exists, seen from a module of ``fluid/``."""
    pkg = FLUID
    for _ in range(level - 1):
        pkg = os.path.dirname(pkg)
    for part in dotted.split('.') if dotted else ():
        if not _exists(pkg, part):
            return False
        pkg = os.path.join(pkg, part)
    # a module binds what it likes; a package has to hold `name` as a
    # submodule or bind it in its __init__
    return not os.path.isdir(pkg) or name == '*' or \
        _exists(pkg, name) or _binds(pkg, name)


@pytest.mark.parametrize('module', MODULES)
def test_fluid_imports_resolve(module):
    missing = [r for r in relative_imports(module)
               if not _resolves(*r[1:])]
    assert not missing, '%s.py: %s' % (module, missing)


@pytest.mark.parametrize('module', BASE)
def test_base_imports_nothing_above(module):
    above = {t: line for t, line in fluid_targets(module).items()
             if t not in ('flags', 'monitor')}
    assert not above, '%s.py imports %s' % (module, above)
    outside = [r for r in relative_imports(module) if r[1] > 1]
    assert not outside, outside


def test_the_cycles_that_remain_are_the_ones_the_roadmap_names():
    """ROADMAP D11.  A PR that cuts an arrow shrinks this list; none
    adds to it."""
    assert cycles(fluid_import_graph()) == [
        ['comms', 'compile_cache', 'memviz', 'profiler'],
        ['executor', 'health', 'parallel_executor', 'supervisor'],
        ['elastic', 'io']]


if __name__ == '__main__':
    g = fluid_import_graph()
    for comp in cycles(g):
        print('cycle of %d: %s' % (len(comp), ' '.join(comp)))
        for m in comp:
            for t, line in sorted(g[m].items()):
                if t in comp:
                    print('  fluid/%s.py:%d -> %s' % (m, line, t))
