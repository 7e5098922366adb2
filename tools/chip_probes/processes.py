"""One-off: what do `pio deploy --fleet 2` and `pio launch -n 2` do on this
host, unguarded (the mechanisms called directly) and guarded (the verbs)?
This parent never imports jax."""
import json, os, signal, socket, subprocess, sys, time, urllib.request
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WD = os.path.join(REPO, ".chip_smoke", "probe")
os.makedirs(WD, exist_ok=True)
env = dict(os.environ)
env.update({
    "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    "PIO_FS_BASEDIR": os.path.join(WD, "pio_store"),
    "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
    "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(WD, "pio.db"),
    "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
    "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(WD, "models"),
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
})
PIO = [sys.executable, "-m", "predictionio_tpu.tools.cli"]
out = {}

def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0)); return s.getsockname()[1]

def run(argv, timeout, **kw):
    t0 = time.time()
    p = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, start_new_session=True, **kw)
    try:
        o, _ = p.communicate(timeout=timeout); timed_out = False
    except subprocess.TimeoutExpired:
        os.killpg(p.pid, signal.SIGKILL); o, _ = p.communicate(); timed_out = True
    return {"rc": p.returncode, "seconds": round(time.time() - t0, 1), "timed_out": timed_out, "tail": o[-1500:]}

# 1. a tiny trained engine (one child at a time: each exits before the next)
eng = os.path.join(WD, "engine"); os.makedirs(eng, exist_ok=True)
json.dump({"id": "default", "engineFactory": "predictionio_tpu.templates.recommendation.RecommendationEngine",
           "datasource": {"params": {"appName": "probeapp"}},
           "algorithms": [{"name": "als", "params": {"rank": 10, "numIterations": 2}}]}, open(os.path.join(eng, "engine.json"), "w"))
seed_script = """
import numpy as np, sys
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.data.storage import App
from predictionio_tpu.data.event import Event
s = Storage.instance(); app_id = s.get_meta_data_apps().insert(App(0, "probeapp")); s.get_l_events().init(app_id)
rng = np.random.default_rng(0)
evs = [Event(event="rate", entity_type="user", entity_id=f"u{rng.integers(0,200)}", target_entity_type="item", target_entity_id=f"i{rng.integers(0,100)}", properties={"rating": float(rng.integers(1,6))}) for _ in range(4000)]
s.get_p_events().write(evs, app_id); print("seeded", len(evs))
"""
out["seed"] = run([sys.executable, "-c", seed_script], 120)
out["train_single"] = run(PIO + ["train", "--engine-dir", eng], 300)
print("train_single", out["train_single"]["rc"], out["train_single"]["seconds"], flush=True)

# 2. unguarded fleet mechanism: two `pio deploy --batching` children, as FleetSupervisor spawns them
ports = [free_port(), free_port()]
kids = [subprocess.Popen(PIO + ["deploy", "--engine-dir", eng, "--ip", "127.0.0.1", "--port", str(p), "--batching"], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, start_new_session=True) for p in ports]
t0 = time.time(); state = [None, None]
while time.time() - t0 < 120 and any(s is None for s in state):
    for i, (k, p) in enumerate(zip(kids, ports)):
        if state[i] is not None: continue
        if k.poll() is not None:
            state[i] = {"exited": k.returncode, "after_s": round(time.time() - t0, 1)}
            continue
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{p}/readyz", timeout=2) as r:
                state[i] = {"ready": json.loads(r.read()).get("fastpathWarm"), "after_s": round(time.time() - t0, 1)}
        except Exception:
            pass
    time.sleep(1)
for i, k in enumerate(kids):
    if state[i] is None: state[i] = {"neither_ready_nor_exited_after_s": 120}
    if k.poll() is None: os.killpg(k.pid, signal.SIGKILL)
    o, _ = k.communicate(); state[i]["tail"] = o[-1200:]
out["two_deploy_children_unguarded"] = state
print("two deploy children", json.dumps([{k: v for k, v in s.items() if k != "tail"} for s in state]), flush=True)

# 3. unguarded launch mechanism: launcher.launch_local with 2 workers
out["launch_local_unguarded"] = run([sys.executable, "-c",
    "import sys; from predictionio_tpu.tools import launcher; sys.exit(launcher.launch_local(['train','--engine-dir',%r], 2, %d))" % (eng, free_port())], 240)
print("launch_local", out["launch_local_unguarded"]["rc"], out["launch_local_unguarded"]["seconds"], out["launch_local_unguarded"]["timed_out"], flush=True)

# 4. the verbs, guarded
out["deploy_fleet_2_verb"] = run(PIO + ["deploy", "--engine-dir", eng, "--ip", "127.0.0.1", "--port", str(free_port()), "--fleet", "2", "--batching"], 180)
out["launch_n2_verb"] = run(PIO + ["launch", "--num-processes", "2", "--coordinator-port", str(free_port()), "--", "train", "--engine-dir", eng], 180)
for k in ("deploy_fleet_2_verb", "launch_n2_verb"):
    print(k, out[k]["rc"], out[k]["seconds"], out[k]["timed_out"], flush=True)
os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
json.dump(out, open(os.path.join(REPO, "chiprun_out", "processes.json"), "w"), indent=1)
print("PROCESSES_DONE")
