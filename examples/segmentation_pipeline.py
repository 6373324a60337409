"""End-to-end segmentation at toy scale: affinity inference -> native
watershed + mean-affinity agglomeration -> connected components -> mesh.

The library-API version of BASELINE config 3 (the CLI spelling is
`... inference ... plugin -f agglomerate connected-components mesh`).
Runs anywhere:

    JAX_PLATFORMS=cpu PYTHONPATH=. \
        python examples/segmentation_pipeline.py
"""
import numpy as np

from chunkflow_tpu import native
from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.chunk.segmentation import Segmentation
from chunkflow_tpu.inference import Inferencer


def main():
    # 1) affinity inference (identity engine keeps the example fast and
    #    deterministic; swap framework="flax", model_variant="rsunet" and
    #    dtype="bfloat16" for the real model)
    rng = np.random.default_rng(0)
    image = rng.random((16, 64, 64)).astype(np.float32)
    inferencer = Inferencer(
        input_patch_size=(8, 32, 32),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=2,
        crop_output_margin=False,
    )
    affs = np.asarray(inferencer(Chunk(image)).array, dtype=np.float32)
    print(f"affinities: {affs.shape} in [{affs.min():.2f}, {affs.max():.2f}]")

    # 2) watershed fragments + hierarchical agglomeration (host C++)
    seg, n_seg = native.watershed_agglomerate(
        affs, t_high=0.9999, t_low=0.2, merge_threshold=0.7
    )
    print(f"agglomeration: {n_seg} segments")

    # 2b) production spelling for MANY chunks: stream(postprocess=...)
    #     runs the host watershed of chunk i in a worker thread while
    #     chunk i+1's program executes on device, so the CPU stage the
    #     reference ships to separate fleets hides behind chip time
    tasks = [Chunk(rng.random((16, 64, 64)).astype(np.float32),
                   voxel_offset=(16 * i, 0, 0)) for i in range(3)]

    def agglomerate(out_chunk):
        arr = np.asarray(out_chunk.array, dtype=np.float32)
        return native.watershed_agglomerate(
            arr, t_high=0.9999, t_low=0.2, merge_threshold=0.7
        )
    for (seg_i, n_i), task in zip(
        inferencer.stream(iter(tasks), postprocess=agglomerate), tasks
    ):
        print(f"  streamed task z={task.voxel_offset[0]}: {n_i} segments")

    # 3) connected components split spatially-disconnected labels
    cc, n_cc = native.connected_components(seg)
    print(f"connected components: {n_cc}")

    # 4) quality metrics against any ground truth (here: itself — 1.0)
    metrics = Segmentation(cc).evaluate(cc)
    print(f"self-ARI sanity: {metrics['adjusted_rand_index']:.3f}")

    # 5) mesh the largest object (surface nets, host C++)
    if n_cc:
        ids, counts = np.unique(cc[cc > 0], return_counts=True)
        obj = int(ids[counts.argmax()])
        verts, faces = native.mesh_object(cc, obj)
        print(f"mesh of object {obj}: {len(verts)} vertices, "
              f"{len(faces)} faces")


if __name__ == "__main__":
    main()
