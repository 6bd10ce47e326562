select d_year, i_category_id, i_category,
       sum(ss_ext_sales_price) total_sales
from date_dim, store_sales, item
where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
  and i_manager_id = 1 and d_moy = 11 and d_year = 2000
group by d_year, i_category_id, i_category
order by total_sales desc, d_year, i_category_id, i_category
limit 100
