"""chip_smoke.py's phases at toy size on the CPU, and its NumPy oracle.

The script itself refuses to run off the TPU; these tests drive its phase
functions through the same user entry points (KmerCounter, count_kmers,
StoreRegistry/QueryService) with the kernels in interpret mode.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import fabsp, serial

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


@pytest.mark.parametrize("k", [5, 15])
@pytest.mark.parametrize("canonical", [False, True])
def test_numpy_oracle_matches_python_oracle(k, canonical):
    reads = chip_smoke.make_reads(5, n_reads=64, genome_bases=512,
                                  read_len=48)
    want = {}
    for w, n in serial.count_kmers_python(reads, k).items():
        if canonical:
            w = min(w, int(chip_smoke.revcomp(np.asarray([w], np.uint32),
                                              k)[0]))
        want[w] = want.get(w, 0) + n
    uniq, counts = serial.count_kmers_numpy(reads, k, canonical=canonical)
    assert uniq.dtype == np.uint32
    assert dict(zip(uniq.tolist(), counts.tolist())) == want


def test_revcomp_is_an_involution():
    w = np.random.default_rng(0).integers(0, 1 << 30, 100).astype(np.uint32)
    assert (chip_smoke.revcomp(chip_smoke.revcomp(w, 15), 15) == w).all()


def test_count_and_serve_phases_exact(tmp_path):
    reads = chip_smoke.make_reads(3, n_reads=512, genome_bases=4096)
    oracle = serial.count_kmers_numpy(reads, chip_smoke.K, canonical=True)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("pe",))
    cfg = fabsp.DAKCConfig(k=chip_smoke.K, canonical=True, chunk_reads=32,
                           spill="auto", spill_dir=str(tmp_path / "spill"))
    kc = chip_smoke.count_phase(mesh, reads, cfg, oracle, tmp_path)
    chip_smoke.serve_phase(mesh, kc, cfg, oracle, tmp_path, 3, flushes=2,
                           requests=2, per_request=300)


def test_count_phase_detects_a_wrong_histogram():
    reads = chip_smoke.make_reads(3, n_reads=64, genome_bases=1024)
    u, c = serial.count_kmers_numpy(reads, chip_smoke.K, canonical=True)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_histogram("x", (u, c + (np.arange(c.size) == 0)),
                                   (u, c))


def test_mesh_phase_on_four_virtual_devices():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax, chip_smoke\n"
        "from repro.core import fabsp, serial\n"
        "reads = chip_smoke.make_reads(1, n_reads=512, genome_bases=4096)\n"
        "oracle = serial.count_kmers_numpy(reads, 15, canonical=True)\n"
        "chip_smoke.mesh_phase(jax.devices()[:4], reads, fabsp.DAKCConfig("
        "k=15, canonical=True, chunk_reads=32), oracle)\n" % str(ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.count("exact against the oracle") == 2
    assert "sharded over 4 chips" in proc.stdout


def test_script_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
