"""Multi-process (DCN-analog) bring-up: `distributed_initialize` with a
REAL 2-process CPU cluster — each subprocess is one "host" owning one
device of a global mesh, and a shard_map psum runs across the process
boundary (the multi-host form of the single-process sharding the rest
of the suite exercises; SURVEY §2.3 distribution row).
"""

import subprocess
import sys
import textwrap

import pytest

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("JAX_NUM_CPU_DEVICES", None)
    os.environ.pop("XLA_FLAGS", None)
    pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    sys.path.insert(0, %(repo)r)
    from siddhi_tpu.parallel import distributed_initialize

    distributed_initialize(
        coordinator_address="127.0.0.1:" + port,
        num_processes=n, process_id=pid)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == n, jax.process_count()
    assert jax.device_count() == n  # one CPU device per process
    mesh = Mesh(np.asarray(jax.devices()), axis_names=("p",))

    # one shard per process; psum crosses the process boundary (DCN)
    local = jnp.full((1, 4), float(pid + 1))
    garr = jax.make_array_from_single_device_arrays(
        (n, 4), NamedSharding(mesh, P("p", None)),
        [jax.device_put(local, jax.local_devices()[0])])

    def f(x):
        return jax.lax.psum(jnp.sum(x), axis_name="p")

    total = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("p", None), out_specs=P()))(garr)
    expect = 4.0 * sum(range(1, n + 1))
    assert float(total) == expect, (float(total), expect)
    print(f"proc {pid} OK psum={float(total)}")
""")


def test_two_process_mesh_psum(tmp_path):
    import socket

    with socket.socket() as s:  # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "worker.py"
    script.write_text(WORKER % {"repo": str(__import__("pathlib").Path(
        __file__).resolve().parent.parent)})
    env = {"PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/tmp"}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), "2", port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed worker timed out")
        outs.append(out.decode())
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} OK psum=12.0" in out, out
