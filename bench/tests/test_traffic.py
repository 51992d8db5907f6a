"""The traffic generator: every seed gets the same work, in other inputs."""

import numpy as np

from bench import traffic_gen


def test_every_seed_serves_the_same_sizes_in_the_same_order():
    mix = traffic_gen.load_mix("azure_code_offline")
    seeds = [0, 7, 2**31 + 5, 2**40 + 3, -12]
    batches = [traffic_gen.lm_batch(mix, s, i, 92544)
               for s in seeds for i in (0, 1)]
    sizes = [[(len(p), n) for p, n in b] for b in batches]
    assert all(s == sizes[0] for s in sizes)
    prompts, outs = traffic_gen.lm_sizes(mix)
    assert sorted(p for p, _ in sizes[0]) == sorted(prompts)
    assert sorted(n for _, n in sizes[0]) == sorted(outs)
    # the seed changes the token ids, and so does the batch index
    first = [b[0][0] for b in batches]
    assert not np.array_equal(first[0], first[2])
    assert not np.array_equal(first[0], first[1])
    assert all(((p >= 0) & (p < 92544)).all() for b in batches for p, _ in b)


def test_stratified_sizes_and_page_counts():
    mix = traffic_gen.load_mix("azure_code_offline")
    prompts, outs = traffic_gen.lm_sizes(mix)
    # 24 mid-quantiles of a log-normal about 1500 (sigma 0.6), rounded up
    # to 512 and held to 512-3072: the middle two straddle the median
    assert prompts == sorted(prompts) and prompts[11:13] == [1536, 2048]
    assert [prompts.count(v) for v in (512, 1024, 1536, 2048, 2560, 3072)] \
        == [1, 5, 6, 5, 3, 4]
    # about 13 (sigma 1.0), held to 2-64
    assert outs[11:13] == [13, 14] and outs[0] == 2 and outs[-1] == 64
    pages = traffic_gen.lm_page_counts(mix, 256)
    for p, n in traffic_gen.lm_batch(mix, 3, 0, 100):
        assert -(-(len(p) + n) // 256) in pages
        assert len(p) + n <= mix["capacity"]


def test_choice_and_uniform_quantiles():
    assert traffic_gen.stratified({"choice": [1, 2], "weights": [3, 1]},
                                  4) == [1, 1, 1, 2]
    assert traffic_gen.stratified({"uniform": [8, 11]}, 4) == [8, 9, 10, 11]


def test_fsi_inputs_are_seeded_binary_and_apart_from_the_warm_up():
    mix = {"batch": 256, "density": 0.3}
    a = traffic_gen.fsi_inputs(mix, 2**33 + 1, 4, 1024)
    assert a.shape == (1024, 256) and a.dtype == np.float32
    assert set(np.unique(a)) <= {0.0, 1.0}
    assert abs(a.mean() - 0.3) < 0.01
    assert np.array_equal(a, traffic_gen.fsi_inputs(mix, 2**33 + 1, 4, 1024))
    assert not np.array_equal(
        a, traffic_gen.fsi_inputs(mix, 2**33 + 1, 4, 1024, warm=True))
    assert not np.array_equal(a, traffic_gen.fsi_inputs(mix, 2**33 + 1, 5,
                                                        1024))
