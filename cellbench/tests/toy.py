"""Toy sizes for the CPU tests: the same files' keys, tiny numbers."""

OPT = {"family": "opt", "hidden_size": 32, "num_attention_heads": 4,
       "ffn_dim": 64, "num_hidden_layers": 2, "vocab_size": 97,
       "max_position_embeddings": 64, "init_std": 0.2,
       "compute_dtype": "bfloat16"}

DECK = {"kind": "serve", "callers": 4, "slots": 2, "max_len": 64,
        "queue_cap": 64, "prompt_lengths": [4, 8, 12, 16],
        "output_lengths": [2, 3, 4, 6], "blocks": 4, "warm_requests": 16,
        "window_opens_after_s": 0, "check_requests": 4,
        # toy limits from toy readings (six seeds): sound runs read
        # gaps of 0 to 0.03, logit_err 0.009 to 0.013 and int8_share
        # -0.04 to 0.15; the program's int8 path reads int8_share
        # 0.82 to 1.15
        "limits": {"gap_widest": 0.05, "gap_mean": 0.005,
                   "logit_err": 0.05, "int8_share": 0.45}}

RESNET = {"family": "resnet", "num_layers": 50, "image_size": 64,
          "num_classes": 10, "compute_dtype": "bfloat16"}

# toy limits from toy readings (three seeds): the bf16 program reads
# update_total_gap up to 2e-4 and the fp8 reference 1e-2 and more
IMAGES = {"kind": "train", "chips": 1, "batch_per_chip": 4, "batches": 4,
          "learning_rate": 0.01, "momentum": 0.9, "weight_decay": 1e-4,
          "check_steps": 3, "warm_epochs": 1, "window_opens_after_s": 0,
          "limits": {"loss_gap": 0.3, "grad_total_gap": 0.05,
                     "update_total_gap": 0.003, "grad_leaf_deficit": 0.6,
                     "update_leaf_deficit": 0.6}}
