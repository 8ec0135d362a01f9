"""Model families of the benchmark, one module each, found by the
`family` key of a configuration file (chipbench/cells.py loads
`families/<family>.py` by path). A family module imports nothing of the
program and gives:

    ARCH_KEYS                  the keys its `architecture` may hold
    SEQUENTIAL_LANES           True: the reference runs the lanes one
                               after another (`lax.map`), where K lanes
                               of activations beside the frozen part do
                               not fit; False: vmapped
    program_architecture(m)    the program's model config
                               (`ExperimentSpec.model_config()`) in the
                               file's architecture keys
    data_maker(config)         the seeded data generator, registered in
                               the program's `experiment.DATASETS`
    labels(data)               (each training row's partition label, the
                               number of labels) for the Dirichlet split
    init(arch, key)            (trained, frozen) weights in float32; only
                               the trained part is differentiated, sent
                               and averaged, the frozen part is shared
                               by every lane and never changes
    loss(trained, frozen, x, y, precision)
    eval_hits(trained, frozen, x, y, precision)
                               (hits, count) over a block of test rows;
                               the accuracy is their sums' ratio
    train_flops_per_sample(arch), eval_flops_per_sample(arch)
                               operations a row, for `mfu`; a frozen
                               weight's gradient is not counted
"""
