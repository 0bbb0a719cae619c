"""Open-loop co-authoring traffic on a document mesh: ``open_loop``'s
schedule, sessions, window and check, with the sidecar's primary plane
split by document over the cell's chips
(``TpuMergeSidecar(doc_mesh=...)``; ``--rehearsal``: over the devices
JAX finds, up to that many).

The sidecar is built before anything else, so a program without the
document mesh fails at once. The window's record gains:
- ``chips``: devices of the mesh;
- ``sidecar_stats``: the window's delta of every ``sidecar.stats`` key
  (per-device lists element by element), from the loop's start to its
  end, before the untimed drain;
- ``table_devices``: devices the live table spans after the drain;
- ``memory_peak_per_device``: each mesh device's peak bytes in use.
"""
from __future__ import annotations

from ..harness import peak_bytes
from . import open_loop


def stats_delta(after: dict, before: dict) -> dict:
    return {k: ([a - b for a, b in zip(v, before[k])]
                if isinstance(v, list) else v - before[k])
            for k, v in after.items()}


class Traffic(open_loop.Traffic):
    def setup(self):
        import jax
        from fluidframework_tpu.ops.bucket_ladder import BucketLadder
        from fluidframework_tpu.parallel.mesh import make_mesh
        from fluidframework_tpu.protocol.messages import ClientDetail
        from fluidframework_tpu.service.sequencer import DocumentSequencer
        from fluidframework_tpu.service.tpu_sidecar import TpuMergeSidecar

        sizes = self.sizes
        self.mesh = make_mesh(jax.devices()[:self.cfg["chips"]])
        # the harness's build_sidecar, on the mesh
        self.sidecar = TpuMergeSidecar(
            max_docs=sizes["docs"], capacity=sizes["capacity"],
            max_capacity=sizes["max_capacity"],
            compact_every=sizes["compact_every"],
            ladder=BucketLadder(window_floor=sizes["window_floor"],
                                max_bucket=sizes["max_bucket"]),
            doc_mesh=self.mesh)
        self.schedule()
        self.record_sessions()
        self.sidecar.prewarm(max_bucket=sizes["max_bucket"])
        self.sequencers = []
        for d in range(self.n_docs):
            doc_id = f"doc-{d}"
            self.sidecar.track(doc_id, "d", "s")
            seq = DocumentSequencer(doc_id)
            for c in range(self.n_clients):
                self.sidecar.ingest(doc_id,
                                    seq.client_join(ClientDetail(f"c{c}")))
            self.sequencers.append(seq)
        self.sidecar.apply()
        self.sidecar.sync()
        return self.sidecar

    def window(self) -> dict:
        sc = self.sidecar
        before = dict(sc.stats)
        at_end = []
        close = self.tracer.close

        def close_at_end(sidecar) -> None:
            # open_loop's window closes the tracer once more where its
            # loop ends, before the drain: the last snapshot is there
            at_end.append(dict(sidecar.stats))
            close(sidecar)

        self.tracer.close = close_at_end
        try:
            rec = super().window()
        finally:
            del self.tracer.close
        rec.update(
            chips=self.mesh.size,
            sidecar_stats=stats_delta(
                at_end[-1] if at_end else dict(sc.stats), before),
            table_devices=len(sc._table.length.sharding.device_set),
            memory_peak_per_device=[peak_bytes(d)
                                    for d in self.mesh.devices.flat])
        return rec
