"""Sizes small enough for the CPU, per family; the widths of a cell are never
changed anywhere else."""

TINY = {
    "lm_train": {
        "config": {"vocab_size": 256, "n_positions": 64, "n_ctx": 64,
                   "n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128,
                   "dtype": "float32"},
        "traffic": {"seq_len": 64, "batch_per_chip": 4, "steps_per_epoch": 4,
                    "reference_micro_rows": 2},
    },
    "vision_train": {
        "config": {"hidden_size": 64, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "intermediate_size": 128,
                   "image_size": 32, "patch_size": 8, "num_labels": 10,
                   "dtype": "float32", "registry_name": "vit_tiny_bench"},
        "traffic": {"batch_per_chip": 8, "steps_per_epoch": 3,
                    "loader_workers": 2, "reference_micro_rows": 4},
    },
}
