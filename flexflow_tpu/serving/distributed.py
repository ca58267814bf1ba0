"""Pod-scale serving placement: the (dp, tp) serving mesh and the
host-partitioned slot/page ownership map.

`optimize_serving` (search/auto.py) picks a decode-optimal (data, model)
mesh — but until `FFModel.compile_for_serving` existed the engine never
executed it: serving inherited whatever sharding the *training* strategy
compiled, on one process's mesh. This module is the missing application
layer, the Orca / FlexFlow-Serve distributed posture on the XLA-native
runtime:

* `build_serving_mesh` builds the (dp, tp) mesh through
  `runtime/multihost.global_mesh` so the outer "data" axis rides DCN
  (crosses hosts) and the inner "model" axis stays on ICI — decode's
  per-token all-reduce over tensor-parallel heads cannot tolerate DCN
  latency, page traffic on the data axis can.
* `ServingPlacement` carries the mesh plus the HOST partition: host h
  owns a contiguous block of request slots and KV pages, mirroring the
  device sharding of pool dim 0 on the "data" axis (NamedSharding
  slices dim 0 contiguously, so device shard boundaries and host
  ownership boundaries coincide). Block tables stay host-local numpy;
  batches are assembled into global arrays through
  `multihost.place_array` (the `place_batch` core).

The degenerate placement (dp = tp = num_hosts = 1) is byte-identical to
the pre-existing single-host engine: one mesh device, fully-replicated
specs, a single host owning every slot and page.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

SERVING_AXES = ("data", "model")

# how the executed mesh was chosen — recorded in exported strategy docs
# so the explain path cannot report a mesh the runtime ignored
MESH_SOURCES = ("flag", "searched", "inherited")


def parse_serve_mesh(text: str) -> Optional[Tuple[int, int]]:
    """Parse a ``--serve-mesh dp,tp`` flag value ('' -> None)."""
    if not text:
        return None
    parts = [p.strip() for p in str(text).split(",")]
    if len(parts) != 2:
        raise ValueError(
            f"--serve-mesh expects 'dp,tp' (got {text!r})"
        )
    try:
        dp, tp = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"--serve-mesh expects two integers 'dp,tp' (got {text!r})"
        )
    if dp < 1 or tp < 1:
        raise ValueError(f"--serve-mesh sizes must be >= 1 (got {text!r})")
    return dp, tp


def build_serving_mesh(dp: int, tp: int):
    """The (data=dp, model=tp) serving mesh over the first dp*tp devices.
    Outer axis on DCN, inner on ICI — see module docstring. Serving may
    use a subset of the machine (the search enumerates divisor counts),
    so the device list is sliced to exactly dp*tp before
    `create_device_mesh` (which requires an exact product)."""
    import jax

    from flexflow_tpu.runtime import multihost

    need = dp * tp
    devices = jax.devices()
    if need > len(devices):
        raise ValueError(
            f"serving mesh (data={dp}, model={tp}) needs {need} devices, "
            f"machine has {len(devices)}"
        )
    return multihost.global_mesh(
        SERVING_AXES, (dp, tp), devices=devices[:need]
    )


def resolve_num_hosts(serve_hosts: int, dp: int) -> int:
    """How many host partitions the scheduler runs. An explicit
    ``--serve-hosts`` wins (simulated hosts on one process — the CPU
    testing posture); otherwise a real multi-process run uses
    `jax.process_count()`; otherwise one host partition per data-axis
    shard (each dp shard's pages live with one host's devices)."""
    if serve_hosts and serve_hosts > 0:
        return int(serve_hosts)
    import jax

    if jax.process_count() > 1:
        return jax.process_count()
    return max(1, int(dp))


@dataclasses.dataclass(frozen=True)
class ServingPlacement:
    """The applied serving mesh + host ownership map.

    `mesh_source` records how (dp, tp) was chosen: "flag"
    (--serve-mesh), "searched" (`search_serving_strategy` winner,
    applied), or "inherited" (no serving mesh — the engine keeps the
    training strategy's sharding; only recorded in docs, a real
    placement is never built inherited)."""

    mesh: object  # jax.sharding.Mesh
    dp: int
    tp: int
    num_hosts: int
    num_heads: int
    mesh_source: str = "flag"

    def kv_sharding(self):
        """NamedSharding of a KV pool, (num_pages, page_size, heads *
        head_dim): pages follow the data axis (host-owned blocks), and
        the model axis cuts the folded last dim into whole heads (it is
        heads-major)."""
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec("data", None, "model"))

    def scale_sharding(self):
        """Quantized-pool scale tables are (num_pages, num_heads)."""
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec("data", "model"))

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def head_sharding(self, heads_dim: int, ndim: int):
        """NamedSharding partitioning axis `heads_dim` of an
        `ndim`-rank weight over the model axis (attention projection
        weights: heads is dim 1 of wq/wk/wv, dim 0 of wo and the
        q/k/v biases)."""
        from jax.sharding import NamedSharding, PartitionSpec

        spec = [None] * ndim
        spec[heads_dim] = "model"
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def kernel_head_shard(self):
        """Where the Pallas decode kernels run on this mesh. A Mosaic
        call has no GSPMD partitioning rule — under plain jit with
        sharded operands JAX refuses it ("Mosaic kernels cannot be
        automatically partitioned") — so a kernel step needs shard_map:

        * one device: None, the kernel is called directly;
        * heads sharded over the model axis, pages whole (data == 1):
          (mesh, "model") — the kernel runs per head shard on that
          shard's slice of q, pools and scales; attention never mixes
          heads, so no collective is needed;
        * pages sharded over the data axis (data > 1): "dense". A
          per-shard kernel would need each slot's pages on its own data
          shard, which the block tables do not promise (num_hosts is
          free to differ from data), and gathering the pool onto every
          chip each step is the silent replication this method exists
          to rule out. The XLA gather path partitions instead."""
        if self.dp > 1:
            return "dense"
        if self.tp > 1:
            return (self.mesh, "model")
        return None

    def validate_geometry(self, max_seqs: int, num_pages: int) -> None:
        """Reject cache geometries the host partition cannot split
        evenly — the runtime mirror of fxlint's FX311/FX312 doc rules."""
        if self.tp >= 1 and self.num_heads % self.tp:
            raise ValueError(
                f"serving mesh model={self.tp} does not divide "
                f"num_heads={self.num_heads}"
            )
        for name, n in (("max_seqs", max_seqs), ("num_pages", num_pages)):
            if n % self.num_hosts:
                raise ValueError(
                    f"serving placement: {name}={n} is not divisible by "
                    f"num_hosts={self.num_hosts} — each host must own an "
                    "equal block"
                )
            if n % self.dp:
                raise ValueError(
                    f"serving placement: {name}={n} is not divisible by "
                    f"data={self.dp} — pool dim 0 shards on the data axis"
                )

    def describe(self) -> str:
        shard = self.kernel_head_shard()
        if shard == "dense":
            attention = (
                "decode attention: dense XLA paths (pool pages are "
                f"sharded over data={self.dp}; the Pallas kernels run "
                "only where a shard holds whole sequences)"
            )
        elif shard is None:
            attention = "decode attention: as ServeConfig.decode_kernel"
        else:
            attention = (
                "decode attention: as ServeConfig.decode_kernel, Pallas "
                f"kernels per head shard under shard_map(model={self.tp})"
            )
        return (
            f"serving placement mesh(data={self.dp}, model={self.tp}) "
            f"[{self.mesh_source}], {self.num_hosts} host partition(s), "
            f"{self.num_heads} heads; {attention}"
        )

    def to_doc(
        self,
        max_seqs: Optional[int] = None,
        num_pages: Optional[int] = None,
    ) -> dict:
        """The exported serving-placement document — validated by fxlint
        `strategy-validate` (FX310-FX312, strategy_check.py)."""
        doc = {
            "version": 1,
            "kind": "serving",
            "mesh_axes": list(SERVING_AXES),
            "mesh_sizes": [self.dp, self.tp],
            "dp": self.dp,
            "tp": self.tp,
            "num_hosts": self.num_hosts,
            "num_heads": self.num_heads,
            "mesh_source": self.mesh_source,
        }
        if num_pages is not None:
            doc["page_pool"] = {
                "num_pages": int(num_pages),
                "pages_per_host": int(num_pages) // self.num_hosts,
            }
        if max_seqs is not None:
            doc["slots"] = {
                "max_seqs": int(max_seqs),
                "slots_per_host": int(max_seqs) // self.num_hosts,
            }
        return doc


def build_placement(
    model,
    dp: int,
    tp: int,
    num_hosts: Optional[int] = None,
    mesh_source: str = "flag",
) -> ServingPlacement:
    """Build the serving mesh and host partition for a compiled model.
    Validates tp against the graph's attention head count before any
    device work (the search already prunes non-dividing tp, but a
    --serve-mesh flag can ask for anything)."""
    from flexflow_tpu.search.auto import _serving_cache_geometry

    _, _, heads, _ = _serving_cache_geometry(model.graph)
    if tp > 1 and heads % tp:
        raise ValueError(
            f"serving mesh model={tp} does not divide the graph's "
            f"num_heads={heads}"
        )
    mesh = build_serving_mesh(dp, tp)
    hosts = resolve_num_hosts(0 if num_hosts is None else num_hosts, dp)
    return ServingPlacement(
        mesh=mesh,
        dp=dp,
        tp=tp,
        num_hosts=hosts,
        num_heads=heads,
        mesh_source=mesh_source,
    )
