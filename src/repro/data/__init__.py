from repro.data.batching import (SizeConstraints, find_size_constraints,  # noqa
                                 merge_graphs, pad_to_sizes)
from repro.data.grouping import BatchPlan, build_batch, merge_and_pad  # noqa
from repro.data.sampling import (GraphStore, InMemorySampler,  # noqa
                                 RANDOM_UNIFORM, SamplingSpec,
                                 SamplingSpecBuilder, distributed_sample,
                                 sample_subgraph, sample_subgraphs,
                                 seed_rng, shard_partition)
from repro.data.serialization import load_graphs, save_graphs  # noqa
from repro.data.pipeline import GraphBatcher, prefetch  # noqa
from repro.data.synthetic import synthetic_mag, token_batches  # noqa
