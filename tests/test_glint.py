"""glint framework tests (ISSUE 11): a positive + negative inline
fixture per pass, suppression and baseline round-trips, the CLI exit
contract, and the tier-1 whole-tree run (zero unsuppressed findings
over the default roots — the machine-checked form of the data-plane
invariants the repo used to enforce by review).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.glint import all_passes  # noqa: E402
from tools.glint.driver import (Run, check_source, load_baseline,  # noqa: E402
                                main, run_glint, write_baseline)


def _src(s: str) -> str:
  return textwrap.dedent(s).lstrip()


def _live(findings):
  return [f for f in findings if f.live]


# -- framework -----------------------------------------------------------------
def test_at_least_six_passes_registered():
  table = all_passes()
  assert len(table) >= 6
  for expected in ('host-sync', 'rng-discipline', 'guarded-by',
                   'monotonic-clock', 'env-knob-drift', 'event-schema',
                   'metric-name'):
    assert expected in table, f'missing pass {expected}'
  for name, cls in table.items():
    assert cls.description, f'{name} has no description'


def test_unknown_rule_is_an_error():
  with pytest.raises(ValueError, match='unknown glint rule'):
    run_glint(rules=['no-such-pass'])


# -- host-sync -----------------------------------------------------------------
HOT_SYNC_BAD = _src('''
    import jax
    import numpy as np
    from graphlearn_tpu.loader.fused import _counted_jit

    def _epoch_fn(state, seeds):
      def body(carry, s):
        carry = carry + s.sum().item()       # sync inside scan body
        return carry, jax.device_get(s)      # sync inside scan body
      out, ys = jax.lax.scan(body, state, seeds)
      np.asarray(out)                        # sync inside jitted fn
      return out

    compiled = _counted_jit(_epoch_fn)
''')

HOT_SYNC_TRANSITIVE = _src('''
    import jax

    def _helper(x):
      return x.block_until_ready()           # hot via transitive call

    def _epoch_fn(state):
      return _helper(state)

    compiled = jax.jit(_epoch_fn)
''')

HOT_SYNC_OK = _src('''
    import jax
    import jax.numpy as jnp
    import numpy as np
    from graphlearn_tpu.loader.fused import _counted_jit

    def _epoch_fn(state, seeds):
      def body(carry, s):
        return carry + jnp.sum(s), s
      return jax.lax.scan(body, state, seeds)

    compiled = _counted_jit(_epoch_fn)

    def host_driver(batch):
      # host-side code may sync freely — it is not in the hot set
      return np.asarray(jax.device_get(batch)).item()
''')


def test_host_sync_positive():
  found = _live(check_source(HOT_SYNC_BAD, 'host-sync'))
  assert len(found) == 3, [f.render() for f in found]
  assert any('.item()' in f.message for f in found)
  assert any('device_get' in f.message for f in found)
  assert any('asarray' in f.message for f in found)


def test_host_sync_transitive_closure():
  found = _live(check_source(HOT_SYNC_TRANSITIVE, 'host-sync'))
  assert len(found) == 1 and 'block_until_ready' in found[0].message


def test_host_sync_negative():
  assert not _live(check_source(HOT_SYNC_OK, 'host-sync'))


def test_host_sync_fori_and_while_bodies():
  """fori_loop/while_loop take their traced callables at positions
  2 and 0/1 — not args[0] like scan (a review catch: the args[0]
  assumption left those bodies unenforced)."""
  src = _src('''
      import jax

      def fbody(i, carry):
        return carry + carry.sum().item()

      def cond(c):
        return bool(c[0])

      def wbody(c):
        return jax.device_get(c)

      def driver(x):
        y = jax.lax.fori_loop(0, 8, fbody, x)
        return jax.lax.while_loop(cond, wbody, y)

      compiled = jax.jit(driver)
  ''')
  found = _live(check_source(src, 'host-sync'))
  assert len(found) == 3, [f.render() for f in found]
  assert any('.item()' in f.message for f in found)
  assert any('bool()' in f.message for f in found)
  assert any('device_get' in f.message for f in found)


# -- rng-discipline ------------------------------------------------------------
RNG_BAD = _src('''
    import jax
    import numpy as np

    def sample(n):
      idx = np.random.permutation(n)         # module-level RandomState
      for i in range(3):
        k = jax.random.PRNGKey(0)            # same key every iteration
      return idx, k
''')

RNG_OK = _src('''
    import jax
    import numpy as np

    def sample(n, seed):
      rng = np.random.default_rng(seed)
      idx = rng.permutation(n)
      base = jax.random.key(seed)
      for i in range(3):
        k = jax.random.fold_in(base, i)
      return idx, k

    def seeded_key_outside_loop():
      return jax.random.PRNGKey(0)           # fine: not in a loop
''')


def test_rng_positive():
  found = _live(check_source(RNG_BAD, 'rng-discipline'))
  assert len(found) == 2, [f.render() for f in found]
  assert any('np.random.permutation' in f.message for f in found)
  assert any('SAME key' in f.message for f in found)


def test_rng_negative():
  assert not _live(check_source(RNG_OK, 'rng-discipline'))


# -- guarded-by ----------------------------------------------------------------
GUARDED_BAD = _src('''
    import threading

    class Counter:
      def __init__(self):
        self._lock = threading.Lock()
        self.served = 0          # guarded-by: self._lock

      def bump(self):
        self.served += 1         # unguarded access

      def wrong_lock(self):
        with self._other_lock:
          self.served += 1       # wrong lock held
''')

GUARDED_OK = _src('''
    import threading

    class Counter:
      def __init__(self):
        self._lock = threading.Lock()
        self.served = 0          # guarded-by: self._lock

      def bump(self):
        with self._lock:
          self.served += 1

      def _bump_locked(self):
        self.served += 1         # *_locked convention: caller holds it

      def helper(self):
        # glint: holds=self._lock
        return self.served

      def unrelated(self):
        return self._lock        # the lock itself is not guarded
''')


def test_guarded_by_positive():
  found = _live(check_source(GUARDED_BAD, 'guarded-by'))
  assert len(found) == 2, [f.render() for f in found]
  assert all('data race' in f.message for f in found)


def test_guarded_by_negative():
  assert not _live(check_source(GUARDED_OK, 'guarded-by'))


# -- monotonic-clock -----------------------------------------------------------
MONO_BAD = _src('''
    import time

    def wait(budget):
      t0 = time.time()                       # flows into arithmetic
      while time.time() - t0 < budget:
        pass
''')

MONO_OK = _src('''
    import time

    def heartbeat():
      return {'at': round(time.time(), 3)}   # pure wall-clock stamp

    def wait(budget):
      deadline = time.monotonic() + budget
      while time.monotonic() < deadline:
        pass
''')


def test_monotonic_positive():
  found = _live(check_source(MONO_BAD, 'monotonic-clock'))
  assert len(found) == 2, [f.render() for f in found]
  assert all('time.monotonic()' in f.message for f in found)


def test_monotonic_negative():
  assert not _live(check_source(MONO_OK, 'monotonic-clock'))


def test_monotonic_sees_import_alias():
  src = _src('''
      import time as _time

      def wait(deadline):
        return _time.time() < deadline
  ''')
  assert len(_live(check_source(src, 'monotonic-clock'))) == 1


# -- env-knob-drift ------------------------------------------------------------
def test_env_knob_positive_and_negative(tmp_path):
  readme = tmp_path / 'README.md'
  readme.write_text('| `GLT_DOCUMENTED` | 1 | a knob |\n')
  run = Run(repo=tmp_path, readme_path=readme)
  src = _src('''
      import os
      a = os.environ.get('GLT_DOCUMENTED', '1')
      b = os.environ.get('GLT_SECRET_KNOB')
  ''')
  found = _live(check_source(src, 'env-knob-drift', run=run))
  assert len(found) == 1 and 'GLT_SECRET_KNOB' in found[0].message
  readme.write_text(readme.read_text()
                    + '| `GLT_SECRET_KNOB` | off | now documented |\n')
  assert not _live(check_source(src, 'env-knob-drift', run=run))


def test_check_env_knobs_shim_still_works():
  """The documented standalone invocation and the helper API
  `tests/test_env_knobs.py` imports must keep working."""
  sys.path.insert(0, str(REPO / 'tools'))
  try:
    import check_env_knobs as shim
  finally:
    sys.path.pop(0)
  refs = shim.knob_references()
  assert 'GLT_FAULT_PLAN' in refs
  assert not shim.undocumented()
  assert shim.main() == 0


# -- event-schema --------------------------------------------------------------
def _schema_fixture(tmp_path, kinds, spans) -> Run:
  schema = tmp_path / 'schema.py'
  fmt = lambda d: '{' + ', '.join(
      f'{k!r}: {v!r}' for k, v in d.items()) + '}'
  schema.write_text(f'EVENT_KINDS = {fmt(kinds)}\n'
                    f'SPAN_NAMES = {fmt(spans)}\n')
  return Run(repo=tmp_path, schema_path=schema, pkg_prefix='pkg')


def test_event_schema_positive(tmp_path):
  run = _schema_fixture(
      tmp_path,
      kinds={'known.kind': 'emitter: field summary',
             'stale.kind': 'emitter: nothing emits this anymore',
             'undocumented.kind': 'short'},
      spans={'known.span': 'emitter: span summary'})
  src = _src('''
      def go(recorder, span):
        recorder.emit('known.kind', x=1)
        recorder.emit('undocumented.kind')
        recorder.emit('rogue.kind', y=2)
        with span('known.span'):
          pass
        with span('rogue.span'):
          pass
  ''')
  found = _live(check_source(src, 'event-schema', rel='pkg/mod.py',
                             run=run))
  msgs = '\n'.join(f.render() for f in found)
  assert len(found) == 4, msgs
  assert "emit('rogue.kind')" in msgs
  assert "'stale.kind'" in msgs and 'no remaining' in msgs
  assert "'undocumented.kind'" in msgs and 'consumer contract' in msgs
  assert "'rogue.span'" in msgs


def test_event_schema_negative(tmp_path):
  run = _schema_fixture(tmp_path,
                        kinds={'known.kind': 'emitter: field summary'},
                        spans={})
  src = "def go(r):\n  r.emit('known.kind', x=1)\n"
  assert not _live(check_source(src, 'event-schema', rel='pkg/mod.py',
                                run=run))


def test_event_schema_ignores_non_package_files(tmp_path):
  run = _schema_fixture(tmp_path, kinds={}, spans={})
  src = "def go(r):\n  r.emit('adhoc.test.kind', x=1)\n"
  assert not _live(check_source(src, 'event-schema',
                                rel='tests/mod.py', run=run))


# -- metric-name ---------------------------------------------------------------
def _metric_fixture(tmp_path, names) -> Run:
  schema = tmp_path / 'schema.py'
  table = '{' + ', '.join(f'{k!r}: {v!r}'
                          for k, v in names.items()) + '}'
  schema.write_text(f'METRIC_NAMES = {table}\n')
  return Run(repo=tmp_path, schema_path=schema, pkg_prefix='pkg')


def test_metric_name_positive(tmp_path):
  run = _metric_fixture(tmp_path, {
      'serving.good_total': 'counter: requests served by the tier',
      'serving.depth': 'gauge: queue depth at scrape time',
      'stale.metric_total': 'counter: nothing registers this anymore',
      'bad.doc_total': 'short',
  })
  src = _src('''
      def wire(live):
        live.counter('serving.good_total')
        live.counter('rogue.metric_total')
        live.counter('NotSnake.Dot')
        live.histogram('serving.depth')
        live.gauge('bad.doc_total')
  ''')
  found = _live(check_source(src, 'metric-name', rel='pkg/mod.py',
                             run=run))
  msgs = '\n'.join(f.render() for f in found)
  # rogue (undeclared), NotSnake.Dot (shape + undeclared), depth
  # registered as histogram but declared gauge, bad.doc_total's
  # declaration malformed, stale.metric_total unregistered
  assert "counter('rogue.metric_total')" in msgs
  assert 'not a snake.dot' in msgs
  assert "declares it 'gauge'" in msgs
  assert "'stale.metric_total'" in msgs and 'no remaining' in msgs
  assert "'bad.doc_total'" in msgs and 'scrape contract' in msgs
  assert len(found) == 6, msgs


def test_metric_name_negative(tmp_path):
  run = _metric_fixture(tmp_path, {
      'serving.good_total': 'counter: requests served by the tier',
      'serving.lat': 'histogram: request latency in log2 buckets',
  })
  src = _src('''
      def wire(live, cap):
        live.counter('serving.good_total', labels={'reason': 'x'})
        live.histogram('serving.lat', labels={'bucket': cap})
  ''')
  assert not _live(check_source(src, 'metric-name', rel='pkg/mod.py',
                                run=run))


def test_metric_name_ignores_non_package_files(tmp_path):
  run = _metric_fixture(tmp_path, {})
  src = "def go(reg):\n  reg.counter('adhoc.test_total')\n"
  assert not _live(check_source(src, 'metric-name',
                                rel='tests/mod.py', run=run))


# -- metric-label-cardinality --------------------------------------------------
def _label_fixture(tmp_path, labels) -> Run:
  schema = tmp_path / 'schema.py'
  table = '{' + ', '.join(f'{k!r}: {v!r}'
                          for k, v in labels.items()) + '}'
  schema.write_text(f'METRIC_LABELS = {table}\n')
  return Run(repo=tmp_path, schema_path=schema, pkg_prefix='pkg')


def test_metric_label_positive(tmp_path):
  run = _label_fixture(tmp_path, {
      'stale_key': 'nothing labels with this anymore',
      'short_doc': 'tiny',
  })
  src = _src('''
      mystery = compute_labels()

      def wire(live, key):
        live.counter('a.b_total', labels={'rogue': 'x'})
        live.counter('a.c_total', labels={key: 'x'})
        live.gauge('a.d', labels=mystery)
        live.counter('a.e_total', labels={'short_doc': 'x'})
  ''')
  found = _live(check_source(src, 'metric-label-cardinality',
                             rel='pkg/mod.py', run=run))
  msgs = '\n'.join(f.render() for f in found)
  # rogue undeclared, {key: ...} non-constant key, `mystery` neither
  # a param nor a unique dict assignment, stale_key unregistered,
  # short_doc's doc too short to state the bounded domain
  assert "'rogue'" in msgs and 'not declared' in msgs
  assert 'non-string-constant' in msgs
  assert "'mystery'" in msgs and 'unique dict literal' in msgs
  assert "'stale_key'" in msgs and 'no remaining' in msgs
  assert "'short_doc'" in msgs and 'cardinality contract' in msgs
  assert len(found) == 5, msgs


def test_metric_label_negative(tmp_path):
  run = _label_fixture(tmp_path, {
      'scope': 'cache scope: one of four fixed cache flavors',
      'bucket': 'bucket capacity: bounded by the serving ladder',
      'window': 'SLO window: bounded by the configured tuple',
  })
  # the four clean conventions: literal dict (dynamic VALUE is fine),
  # positional dict, a forwarding helper whose labels is a parameter,
  # and a bare name bound once to a dict literal in the same file
  src = _src('''
      def helper(live, name, labels, fn):
        live.gauge(name, labels=labels, fn=fn)

      def wire(live, cap, scope):
        live.histogram('a.lat', labels={'bucket': cap})
        live.gauge('a.burn', {'window': '60s'}, lambda: 1.0)
        live.counter('a.plain_total', labels=None)
        labels = {'scope': scope}
        live.counter('a.hits_total', labels=labels)
        helper(live, 'a.g', {'window': '300s'}, lambda: 2.0)
  ''')
  assert not _live(check_source(src, 'metric-label-cardinality',
                                rel='pkg/mod.py', run=run))


def test_metric_label_forbidden_trace_keys(tmp_path):
  # trace_id/span_id are forbidden regardless of schema declarations
  # — a per-request id label mints one series per request, the exact
  # leak exemplars exist to avoid (ISSUE 17)
  run = _label_fixture(tmp_path, {})
  src = _src('''
      def wire(live, tid, sid):
        live.histogram('a.lat', labels={'trace_id': tid})
        live.counter('a.spans_total', labels={'span_id': sid})
  ''')
  found = _live(check_source(src, 'metric-label-cardinality',
                             rel='pkg/mod.py', run=run))
  msgs = '\n'.join(f.render() for f in found)
  assert "'trace_id'" in msgs and 'forbidden label key' in msgs
  assert "'span_id'" in msgs and 'exemplars' in msgs
  assert len(found) == 2, msgs


def test_metric_label_forbidden_keys_negative(tmp_path):
  # exemplar plumbing that never makes trace_id a label KEY is clean:
  # the id rides `observe(..., exemplar=tid)`, not the series space
  run = _label_fixture(tmp_path, {
      'bucket': 'bucket capacity: bounded by the serving ladder',
  })
  src = _src('''
      def wire(live, cap, tid):
        h = live.histogram('a.lat', labels={'bucket': cap})
        h.observe(0.25, exemplar=tid)
  ''')
  assert not _live(check_source(src, 'metric-label-cardinality',
                                rel='pkg/mod.py', run=run))


def test_metric_label_ignores_non_package_files(tmp_path):
  run = _label_fixture(tmp_path, {})
  src = "def go(reg):\n  reg.counter('x.y_total', labels={'z': 1})\n"
  assert not _live(check_source(src, 'metric-label-cardinality',
                                rel='tests/mod.py', run=run))


# -- suppressions --------------------------------------------------------------
def test_inline_suppression_trailing_and_standalone():
  src = _src('''
      import time

      def wait(budget):
        t0 = time.time()  # glint: disable=monotonic-clock
        # glint: disable=monotonic-clock
        while time.time() - t0 < budget:
          pass
  ''')
  found = check_source(src, 'monotonic-clock')
  assert len(found) == 2
  assert all(f.suppressed for f in found), [f.render() for f in found]
  assert not _live(found)


def test_suppression_is_rule_specific():
  src = _src('''
      import time

      def wait(budget):
        t0 = time.time()  # glint: disable=some-other-rule
        return time.time() - t0 < budget
  ''')
  assert len(_live(check_source(src, 'monotonic-clock'))) == 2


# -- baseline ------------------------------------------------------------------
def _violation_tree(tmp_path) -> Run:
  mod = tmp_path / 'pkg'
  mod.mkdir()
  (mod / 'clock.py').write_text(_src('''
      import time

      def wait(budget):
        t0 = time.time()
        return time.time() - t0 < budget
  '''))
  readme = tmp_path / 'README.md'
  readme.write_text('no knobs\n')
  schema = tmp_path / 'schema.py'
  schema.write_text('EVENT_KINDS = {}\nSPAN_NAMES = {}\n')
  return Run(repo=tmp_path, readme_path=readme, schema_path=schema,
             pkg_prefix='pkg')


def test_baseline_round_trip(tmp_path):
  run = _violation_tree(tmp_path)
  findings = run_glint(paths=['pkg'], run=run)
  assert len(_live(findings)) == 2
  bl = tmp_path / 'baseline.json'
  write_baseline(bl, findings)
  assert len(load_baseline(bl)) == 2
  again = run_glint(paths=['pkg'], run=run, baseline=bl)
  assert not _live(again)
  assert all(f.baselined for f in again)


def test_baseline_is_a_multiset(tmp_path):
  """One grandfathered instance must not absolve a SECOND copy of the
  same pattern added later."""
  run = _violation_tree(tmp_path)
  bl = tmp_path / 'baseline.json'
  write_baseline(bl, run_glint(paths=['pkg'], run=run))
  src = (tmp_path / 'pkg' / 'clock.py').read_text()
  (tmp_path / 'pkg' / 'clock.py').write_text(
      src + '\n\ndef wait2(budget):\n  t0 = time.time()\n'
            '  return time.time() - t0 < budget\n')
  again = run_glint(paths=['pkg'], run=run, baseline=bl)
  assert len(_live(again)) == 2, [f.render() for f in again]


def test_baseline_survives_line_shift(tmp_path):
  run = _violation_tree(tmp_path)
  bl = tmp_path / 'baseline.json'
  write_baseline(bl, run_glint(paths=['pkg'], run=run))
  path = tmp_path / 'pkg' / 'clock.py'
  path.write_text('# a new comment shifting every line\n'
                  + path.read_text())
  again = run_glint(paths=['pkg'], run=run, baseline=bl)
  assert not _live(again)


# -- CLI -----------------------------------------------------------------------
def test_cli_exit_codes(tmp_path, capsys):
  run_dir = _violation_tree(tmp_path)
  del run_dir  # only the tree is needed; CLI builds its own Run
  bad = str(tmp_path / 'pkg' / 'clock.py')
  bl = tmp_path / 'bl.json'
  assert main([bad, '--baseline', str(bl)]) == 1
  # --write-baseline refuses a filtered scope (explicit paths or
  # --rules): a subset run would silently drop every grandfathered
  # entry outside the filter
  assert main([bad, '--baseline', str(bl), '--write-baseline']) == 2
  assert main(['--rules', 'monotonic-clock', '--write-baseline',
               '--baseline', str(bl)]) == 2
  write_baseline(bl, run_glint(paths=[bad]))
  assert main([bad, '--baseline', str(bl)]) == 0
  assert main([bad, '--baseline', str(bl), '--no-baseline']) == 1
  assert main(['--list-passes']) == 0
  assert main([bad, '--rules', 'nope']) == 2
  out = capsys.readouterr().out
  assert 'monotonic-clock' in out


def test_cli_module_entry_point():
  """`python -m tools.glint` is the single documented entry point —
  pin that it imports and exits 0 on the real tree."""
  proc = subprocess.run(
      [sys.executable, '-m', 'tools.glint', '-q'],
      cwd=REPO, capture_output=True, text=True, timeout=120)
  assert proc.returncode == 0, proc.stdout + proc.stderr


# -- the tier-1 whole-tree run -------------------------------------------------
def test_whole_tree_clean():
  """The acceptance invariant: zero unsuppressed, un-baselined
  findings over graphlearn_tpu/ and examples/
  with all >= 6 passes enabled — against the same checked-in baseline
  the CLI honors, so the two documented entry points agree."""
  from tools.glint.driver import DEFAULT_BASELINE
  findings = run_glint(baseline=DEFAULT_BASELINE)
  live = _live(findings)
  assert not live, 'glint findings on the tree:\n' + '\n'.join(
      f.render() for f in live)


def test_whole_tree_is_not_vacuous():
  """Guard the guard: the scan must actually be seeing the tree —
  the fused drivers' hot sets, the guarded-by annotations, and the
  knob vocabulary.  A discovery regression that scanned nothing would
  make test_whole_tree_clean pass vacuously."""
  from tools.glint.driver import DEFAULT_ROOTS, REPO as GREPO, discover
  files = discover(DEFAULT_ROOTS, GREPO)
  rels = {f.relative_to(GREPO).as_posix() for f in files}
  assert len(rels) > 100
  for must in ('graphlearn_tpu/loader/fused.py',
               'graphlearn_tpu/parallel/fused.py',
               'graphlearn_tpu/serving/frontend.py',
               'graphlearn_tpu/distributed/dist_sampling_producer.py',
               'examples/train_sage.py'):
    assert must in rels
