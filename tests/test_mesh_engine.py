"""The serving engine on a four-device batch mesh: placement, the mesh
executable, the split and the guard, and the answers' dtypes.

A mesh plan's micro-batch is padded to a multiple of the mesh size on the
host and placed sharded ``P("batch")``, so each row reaches only the device
of its shard; the executable neither pads nor trims, so it holds no
collective; each answer is split off on its shard's device and stays there,
cast there to ``uint8`` when its frame came as 8-bit levels.
Everything here runs once, in one subprocess with four forced host devices
(the main pytest process keeps its single-device view, as in
test_bg_sharded.py); the tests read what it reports.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.serving import EngineStats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTHS = range(1, 10)
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute")

PROBE = """
import glob, json, os, sys, tempfile
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
sys.path[:0] = [os.path.join(REPO, 'benchmarks', 'chip')]
import jax, jax.numpy as jnp, numpy as np
from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
from repro.plan import plan_for
from repro.reliability.guards import _guard_programs
from repro.serving import AsyncFrameEngine
from repro.serving.device_stages import place_rows, split_rows
from repro.sharding.bg_shard import batch_mesh
import reference

assert jax.device_count() == 4
report = {}
# the filter of the paper's setting (r=12, sigma_s=8, sigma_r=70), small frames
params = {'r': 12, 'sigma_s': 8.0, 'sigma_r': 70.0}
cfg = BGConfig(**params)
h, w = 48, 64
mesh = batch_mesh(4)
plan = plan_for(cfg, h, w, n_frames=9, mesh=mesh, cache=False)
single = plan.with_options(mesh=None)
assert plan.mesh_size == 4 and single.mesh_size == 1
frames = np.clip(np.round(np.asarray(add_gaussian_noise(
    synthetic_batch(9, h, w, seed=3), 30.0, seed=4))), 0, 255).astype(np.uint8)
dev_ids = [d.id for d in mesh.devices.flat]

def serve(k, rows=frames):
    with AsyncFrameEngine(plan=plan, max_batch=k, batch_window_ms=60000.0) as eng:
        outs = [f.result(timeout=600) for f in [eng.submit(f) for f in rows[:k]]]
        return outs, eng.stats()

worst, largest = 0.0, 0.0
refs = reference.denoise(frames, params)
for k in LENGTHS:
    outs, st = serve(k)
    want = np.asarray(single(jnp.asarray(frames[:k])))
    got = np.stack([np.asarray(o) for o in outs])
    per = -(-k // 4)
    diff = np.abs(got - refs[:k].astype(np.float32))
    worst = max(worst, float(np.mean(diff != 0, axis=(1, 2)).max()))
    largest = max(largest, float(diff.max()))
    report[str(k)] = {
        'bitwise': bool(np.array_equal(got, want)),
        'answer_devices': [sorted(d.id for d in o.devices()) for o in outs],
        'shard_devices': [[dev_ids[j // per]] for j in range(k)],
        'dispatches': st.dispatches, 'mesh_pad_rows': st.mesh_pad_rows,
        'pad_in_dict': st.as_dict()['mesh_pad_rows'],
        'dtypes': [str(o.dtype) for o in outs], 'level_answers': st.level_answers,
    }
report['reference'] = {'off_share_worst': worst, 'max_abs_diff': largest}

# float32 frames, and a batch that mixes them with uint8 ones, over shards
want = np.asarray(single(jnp.asarray(frames[:6])))
for name, rows in (('float32', list(frames[:6].astype(np.float32))),
                   ('mixed', [f.astype(np.float32) if i % 2 else f
                              for i, f in enumerate(frames[:6])])):
    outs, st = serve(6, rows)
    report['dtypes_' + name] = {
        'dtypes': [str(o.dtype) for o in outs], 'level_answers': st.level_answers,
        'equal': bool(np.array_equal(np.stack([np.asarray(o) for o in outs]), want)),
        'answer_devices': [sorted(d.id for d in o.devices()) for o in outs],
    }

def found(text):
    return [c for c in COLLECTIVES if c in text]

for k in (6, 8):
    placed, pad = place_rows(list(frames[:k]), mesh)
    out = plan(placed)
    per = placed.shape[0] // 4
    shards = sorted(out.addressable_shards, key=lambda s: s.index[0].start)
    report['programs_%d' % k] = {
        'executable': found(plan.executable().lower(placed).compile().as_text()),
        'guard': found(_guard_programs()[0].lower(out).compile().as_text()),
        'split': sorted({c for s in shards if min(per, k - s.index[0].start) > 0
                         for c in found(split_rows.lower(
                             s.data, min(per, k - s.index[0].start),
                             (np.dtype(np.uint8),) * min(per, k - s.index[0].start)
                         ).compile().as_text())}),
        'out_spec': str(out.sharding.spec),
    }
    report['placed_%d' % k] = {
        'pad': pad, 'shape': list(placed.shape), 'dtype': str(placed.dtype),
        'replicated': placed.sharding.is_fully_replicated,
        'shards': [{'devices': sorted(d.id for d in s.data.devices()),
                    'device': s.device.id, 'start': s.index[0].start,
                    'rows_equal': bool(np.array_equal(
                        np.asarray(s.data),
                        np.concatenate([frames[:k], np.zeros((placed.shape[0] - k, h, w),
                                                            np.uint8)])[s.index[0]]))}
                   for s in placed.addressable_shards],
    }

# device rows move chip to chip: rows 0-4 on device 0 (rows 2-4 belong to
# other shards), as float32, with host uint8 rows after them; each row is
# handed to jax.device_put as it came, device rows as device arrays
mixed = [jax.device_put(jnp.asarray(f, jnp.float32), jax.devices()[0]) for f in frames[:5]]
put, puts = jax.device_put, []
def recording_put(x, device=None, **kw):
    for r in (x if isinstance(x, list) else [x]):
        puts.append({'device_array': isinstance(r, jax.Array), 'to': device.id,
                     'from': sorted(d.id for d in r.devices()) if isinstance(r, jax.Array) else None})
    return put(x, device, **kw)
jax.device_put = recording_put
try:
    placed, pad = place_rows(mixed + list(frames[5:7]), mesh)
finally:
    jax.device_put = put
report['device_rows'] = {
    'puts': sorted(puts, key=lambda p: (p['to'], not p['device_array'])),
    'pad': pad, 'shape': list(placed.shape), 'dtype': str(placed.dtype),
    'devices': sorted(sorted(d.id for d in s.data.devices()) + [s.device.id]
                      for s in placed.addressable_shards),
    'rows_equal': bool(np.array_equal(np.asarray(placed), np.concatenate(
        [frames[:7], np.zeros((1, h, w), np.uint8)]).astype(np.float32))),
    'answers_equal': bool(np.array_equal(np.asarray(plan(placed)[:7]),
                                         np.asarray(single(jnp.asarray(frames[:7]))))),
}

# the engine's spans under the profiler: two dispatches of 3 frames, 1 pad row each
trace = tempfile.mkdtemp()
jax.profiler.start_trace(trace)
try:
    with AsyncFrameEngine(plan=plan, max_batch=3, batch_window_ms=60000.0) as eng:
        [f.result(timeout=600) for f in [eng.submit(f) for f in frames[:6]]]
finally:
    jax.profiler.stop_trace()
path = glob.glob(os.path.join(trace, '**', '*.xplane.pb'), recursive=True)[0]
spans = []
for plane in jax.profiler.ProfileData.from_file(path).planes:
    for line in plane.lines:
        for e in line.events:
            if e.name in ('engine.prep', 'engine.place'):
                a = dict(e.stats)
                spans.append({'name': e.name, 'line': plane.name + '/' + line.name,
                              'start': e.start_ns, 'end': e.start_ns + e.duration_ns,
                              'dispatch': int(a['dispatch']), 'n': int(a['n']),
                              'pad': int(a['pad']) if 'pad' in a else None})
report['spans'] = spans

# the benchmark's cell itself, at a small size, on the four host devices
import harness
res = harness.run('paper1080-batch-mesh4', 2**31 + 11, 1.0, False,
                  overrides={'config': {'height': h, 'width': w},
                             'traffic': {'pool_frames': 8, 'outstanding': 8,
                                         'engine': {'max_batch': 6}}},
                  devices=jax.devices(), log=lambda m: None)
report['cell'] = {'correct': res['correct'], 'count': res['device']['count'],
                  'checks': {k: v['value'] for k, v in res['checks'].items()},
                  'metrics': sorted(res['metrics'])}
print('REPORT ' + json.dumps(report))
"""


@pytest.fixture(scope="module")
def report():
    code = (f"REPO = {REPO!r}\nLENGTHS = {tuple(LENGTHS)!r}\n"
            f"COLLECTIVES = {COLLECTIVES!r}\n" + textwrap.dedent(PROBE))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout[-3000:]}\nSTDERR:\n{out.stderr[-3000:]}"
    line = next(s for s in out.stdout.splitlines() if s.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


@pytest.mark.parametrize("k", LENGTHS)
def test_mesh_answers_are_bitwise_the_one_device_plans(report, k):
    assert report[str(k)]["bitwise"]
    assert report[str(k)]["dispatches"] == 1


def test_mesh_answers_agree_with_the_reference_within_the_limits(report):
    # the limits of benchmarks/chip/configs/paper-1080p-r12-mesh4.json
    assert report["reference"]["off_share_worst"] <= 0.003
    assert report["reference"]["max_abs_diff"] <= 2.0


@pytest.mark.parametrize("k", (6, 8))
def test_mesh_programs_hold_no_collective(report, k):
    progs = report[f"programs_{k}"]
    assert progs["executable"] == [] and progs["guard"] == [] and progs["split"] == []
    assert progs["out_spec"] == "PartitionSpec('batch',)"


@pytest.mark.parametrize("k", (6, 8))
def test_placed_rows_live_on_their_shards_device_only(report, k):
    placed = report[f"placed_{k}"]
    assert placed["pad"] == 8 - k
    assert placed["shape"][0] == 8 and placed["dtype"] == "uint8"
    assert not placed["replicated"]
    shards = placed["shards"]
    assert sorted(s["device"] for s in shards) == [0, 1, 2, 3]
    assert sorted(s["start"] for s in shards) == [0, 2, 4, 6]
    for s in shards:
        assert s["devices"] == [s["device"]] and s["rows_equal"]


def test_device_rows_reach_their_shards_without_the_host(report):
    rows = report["device_rows"]
    # rows 0-1 stay on device 0, 2-3 go to device 1 and 4 to device 2, each
    # straight from device 0; host rows 5-6 go to device 2 and 3
    dev = lambda to: {"device_array": True, "to": to, "from": [0]}
    host = lambda to: {"device_array": False, "to": to, "from": None}
    assert rows["puts"] == [dev(0), dev(0), dev(1), dev(1), dev(2), host(2), host(3)]
    assert rows["pad"] == 1 and rows["shape"][0] == 8 and rows["dtype"] == "float32"
    assert rows["devices"] == [[0, 0], [1, 1], [2, 2], [3, 3]]
    assert rows["rows_equal"] and rows["answers_equal"]


@pytest.mark.parametrize("k", LENGTHS)
def test_mesh_answers_of_uint8_frames_are_uint8(report, k):
    r = report[str(k)]
    assert r["dtypes"] == ["uint8"] * k and r["level_answers"] == k


@pytest.mark.parametrize("name,dtypes", [
    ("float32", ["float32"] * 6),
    ("mixed", ["uint8", "float32"] * 3),
])
def test_mesh_answer_dtype_follows_its_own_frame(report, name, dtypes):
    r = report["dtypes_" + name]
    assert r["dtypes"] == dtypes and r["equal"]
    assert r["level_answers"] == dtypes.count("uint8")
    # rows 0-1 on device 0, 2-3 on 1, 4-5 on 2: each answer on its shard's
    assert r["answer_devices"] == [[0], [0], [1], [1], [2], [2]]


def test_each_answer_lives_on_its_shards_device_only(report):
    for k in LENGTHS:
        r = report[str(k)]
        assert r["answer_devices"] == r["shard_devices"], k


def test_mesh_pad_rows_counts_the_pad_rows(report):
    for k in LENGTHS:
        r = report[str(k)]
        assert r["mesh_pad_rows"] == r["pad_in_dict"] == -k % 4, k
    parts = [EngineStats(1, 1, 1, 0, 0, 0, 1.0, 1.0, 1.0, mesh_pad_rows=p) for p in (3, 0, 2)]
    assert EngineStats.merge(parts).mesh_pad_rows == 5
    old = parts[0].as_dict()
    del old["mesh_pad_rows"]  # a worker that predates the field
    assert EngineStats(**old).mesh_pad_rows == 0


def test_engine_place_span_reaches_the_profiler_inside_prep(report):
    prep = {s["dispatch"]: s for s in report["spans"] if s["name"] == "engine.prep"}
    place = [s for s in report["spans"] if s["name"] == "engine.place"]
    assert sorted(prep) == [0, 1]
    assert sorted((s["dispatch"], s["n"], s["pad"]) for s in place) == [(0, 3, 1), (1, 3, 1)]
    for s in place:
        outer = prep[s["dispatch"]]
        assert s["line"] == outer["line"]
        assert outer["start"] <= s["start"] <= s["end"] <= outer["end"]


def test_the_mesh_cell_runs_correct_on_four_host_devices(report):
    cell = report["cell"]
    assert cell["correct"] and cell["count"] == 4
    assert cell["checks"]["lost_frames"] == 0 and cell["checks"]["fallbacks"] == 0
    assert cell["checks"]["compared_frames"] == 24
    assert {"frames_per_s", "setup_s"} <= set(cell["metrics"])
