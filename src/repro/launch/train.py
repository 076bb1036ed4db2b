"""End-to-end training driver — a thin client of ``repro.api``.

  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --sync optinc --steps 200 --global-batch 32 --seq-len 512 \
      --ckpt-dir results/ckpt/paper_llama [--resume] [--error-layers 3,4,5,6]

  # two-level carry-cascade over a (pod=2, data=2, model=1) mesh
  # (requires >= 4 devices, e.g. XLA_FLAGS=--xla_force_host_platform_device_count=4)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync cascade --mesh 2x1 --bucket-mb 4

  # streaming engine: buckets dispatch in gradient-readiness order so
  # collectives overlap the remaining backward (bit-identical losses to
  # the barrier path — EXPERIMENTS.md §Overlap)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync cascade --mesh 2x1 --overlap

  # hardware-in-the-loop: the MZI mesh emulator computes the averaged
  # gradient inside the jitted step (--fidelity onn uses the dense ONN;
  # bits<=2 resolves the built-in exact identity ONN, wider bit widths
  # need trained params — see repro.photonics.runtime)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync optinc --bits 2 --fidelity mesh

  # same, with the emulator's rotation layers fused into one Pallas
  # VMEM kernel per batch tile (compiled on TPU, interpreted elsewhere)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync optinc --bits 2 --fidelity mesh \
      --mesh-backend pallas

  # two-level photonic cascade: BOTH reduction levels run the mesh
  # emulator, the eq.-10 carry symbol threaded between them (bit-exact
  # vs --fidelity behavioral on the built-in exact ONN at bits<=2)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync cascade --mesh 2x1 --bits 2 --fidelity mesh

  # thermal drift + shot noise on the emulated mesh (PhaseNoise model,
  # seeded from the per-step key: reproducible, identical across hosts)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync optinc --bits 2 --fidelity mesh \
      --theta-drift-std 0.02 --shot-noise-std 0.01

  # elastic membership: world size becomes a runtime property — the run
  # watches the member registry, re-derives the cascade topology when a
  # pod drops/joins, and reshard-resumes from the last checkpoint
  # (multi-process agents: python -m repro.elastic.worker)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync cascade --mesh 2x1 --elastic \
      --ckpt-dir results/ckpt/elastic --ckpt-every 1

  # resume a checkpoint on a DIFFERENT mesh shape (compatible-reshard:
  # global state re-placed, error-feedback residuals re-bucketized)
  PYTHONPATH=src python -m repro.launch.train --arch paper_llama \
      --smoke-config --sync cascade --mesh 2x1 --pods 1 \
      --ckpt-dir results/ckpt/elastic --resume --allow-reshard

  # or describe the whole scenario declaratively:
  PYTHONPATH=src python -m repro.launch.train --spec my_run.json

Every flag is a RunSpec field override (``RunSpec.from_args``); the run
itself — mesh/ShardCtx derivation, init-or-resume, the jitted step loop,
JSONL logging, periodic + SIGTERM-safe checkpointing (params, optimizer,
AND error-feedback residuals), straggler watchdog — lives in
``repro.api.TrainSession``.  ``--resume`` validates the checkpointed
RunSpec against this one and restores bit-exactly.
"""
from __future__ import annotations

import sys

from repro.api import RunSpec, SpecError, TrainSession
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    try:
        spec = RunSpec.from_args(argv, description=__doc__)
        if spec.elastic.enabled:
            from repro.elastic import ElasticTrainSession, Membership
            # Single-process elastic run: this process owns the whole
            # mesh, so it self-hosts the registry — one member per rank,
            # all beating from here.  The world forms immediately;
            # membership changes come from suspect tombstones (watchdog
            # --evict-after escalation, or an operator touching
            # <member>.suspect) or from extra agents joining the dir.
            # Multi-process runs use repro.elastic.worker instead, where
            # each process is ONE member and SIGKILL = going stale.
            e = spec.elastic
            ranks = [Membership(e.members_dir(spec.ckpt.dir),
                                member=f"w{i}", heartbeat_s=e.heartbeat_s,
                                timeout_s=e.timeout_s)
                     for i in range(spec.mesh.pods * spec.mesh.dp)]
            for m in ranks:
                m.join()
                m.start_heartbeat()
            try:
                ElasticTrainSession(spec, membership=ranks[0]).run()
            finally:
                for m in ranks:
                    m.stop_heartbeat()
        else:
            TrainSession(spec).run()
    except SpecError as e:
        raise SystemExit(f"error: {e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
