"""shifu-tpu's benchmark: the yardstick lives here, the program under test in ``shifu_tpu/``.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
